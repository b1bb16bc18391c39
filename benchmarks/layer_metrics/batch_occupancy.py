"""engine: continuous batching.  `engine.num_active() / max_batch`, sampled
at 20 Hz in the replica, averaged over the window."""

LAYER = "engine"
UNIT = "%"
MOVES = "out_tokens_per_s"


def read(obs):
    if "samples" not in obs:
        return None
    t0, t1 = obs["window"]
    active = [s[1] for s in obs["samples"] if t0 <= s[0] <= t1]
    if not active:
        return None
    return 100.0 * sum(active) / len(active) / obs["max_batch"]
