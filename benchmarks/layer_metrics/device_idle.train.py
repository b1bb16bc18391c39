"""device: share of the traced steps in which no operation ran on the chip
(1 - union of device operation intervals / window)."""

LAYER = "device"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(obs):
    trace = obs.get("trace")
    if not trace or "steps" not in obs:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
