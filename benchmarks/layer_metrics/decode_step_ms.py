"""model step: one decode step on the device.  Median device duration of
the engine's decode program (`decode_chunk_paged`, or `decode_chunk_fn`
in dense mode) divided by the steps in a chunk."""

from benchmarks.harness import stats

LAYER = "model step"
UNIT = "ms"
MOVES = "tpot_p90_ms"
PROGRAMS = ("decode_chunk_paged", "decode_chunk_fn")


def read(obs):
    trace = obs.get("trace")
    if not trace or "serve" not in obs["config"]:
        return None
    runs = [d for p in PROGRAMS for d in trace["program_ns"].get(p, [])]
    if not runs:
        return None
    chunk = obs["config"]["serve"]["engine"]["decode_chunk"]
    return stats.median(runs) / 1e6 / chunk
