"""model step: one prefill call on the device.  Median device duration of
a `prefill_many` / `prefill_one` run in the traced window."""

from benchmarks.harness import stats

LAYER = "model step"
UNIT = "ms"
MOVES = "ttft_p90_ms"
PROGRAMS = ("prefill_many", "prefill_one")


def read(obs):
    trace = obs.get("trace")
    if not trace:
        return None
    runs = [d for p in PROGRAMS for d in trace["program_ns"].get(p, [])]
    return stats.median(runs) / 1e6 if runs else None
