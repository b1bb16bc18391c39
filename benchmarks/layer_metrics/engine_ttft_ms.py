"""engine: admission + prefill.  Replica side, `engine.submit` -> first
token out of the engine's stream; the median over the window's requests."""

from benchmarks.harness import stats

LAYER = "engine"
UNIT = "ms"
# A prefill holds the device between two decode chunks, so what lengthens
# this lengthens the slowest streams' time per token; the client's TTFT is
# recorded per layer (`client_ttft_p90_ms`), too noisy to be judged.
MOVES = "tpot_p90_ms"


def read(obs):
    ttft = [s["first"] - s["submit"] for s in obs.get("replica_spans", [])
            if s["first"] is not None]
    return stats.median(ttft) * 1e3 if ttft else None
