"""model step: one prefill call on the device.  Median device duration of
a `prefill_many` / `prefill_one` run in the traced window."""

from benchmarks.harness import stats

LAYER = "model step"
UNIT = "ms"
# A prefill holds the device between two decode chunks, so its length is in
# the slowest streams' time per token; the client's TTFT is recorded per
# layer (`client_ttft_p90_ms`), too noisy to be judged end to end.
MOVES = "tpot_p90_ms"
PROGRAMS = ("prefill_many", "prefill_one")


def read(obs):
    trace = obs.get("trace")
    if not trace:
        return None
    runs = [d for p in PROGRAMS for d in trace["program_ns"].get(p, [])]
    return stats.median(runs) / 1e6 if runs else None
