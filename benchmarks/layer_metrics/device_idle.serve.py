"""device: share of the traced window in which no operation ran on the
chip (1 - union of device operation intervals / window)."""

LAYER = "device"
UNIT = "%"
MOVES = "out_tokens_per_s"


def read(obs):
    trace = obs.get("trace")
    if not trace or "client_spans" not in obs:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
