"""model: required operations per token (the benchmark's own count: no
embedding gather, causal attention in, rematerialisation not counted)
times tokens per second per chip, over the chip's peak.  The rate is the
tokens of a step over the median step period (one step's start to the
next's) of the steps the profiler did not touch: starting and stopping
the trace stalls the loop, and that is the tracer's time, not the model's.
"""

from benchmarks.harness import kernel_costs, stats

LAYER = "model"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(obs):
    peak, steps = obs.get("peaks"), obs.get("steps")
    if not peak or not steps:
        return None
    periods = [b["start"] - a["start"] for a, b in zip(steps, steps[1:])
               if not a.get("traced") and not b.get("traced")]
    if not periods:
        return None
    flops = kernel_costs.train_flops_per_token(obs["sizes"], obs["seq"])
    rate = obs["tokens_per_step"] / stats.median(periods) \
        / obs["device"]["count"]
    return 100.0 * flops * rate / peak["bf16_flops_per_s"]
