"""model step: the operations the prompts admitted in the traced slot
REQUIRE (`mla_moe_costs.prefill_flops`: projections and feed-forwards at
every position, EIGHT experts' worth a token a routed layer (six routed,
two shared), the causal half of attention at 192 / 128 a head, the head at
the last token only) over the device time of the prefill programs that ran
in it times the chip's peak: `moe_prefill_mfu.py`'s reader with this
family's costs.  Rows of padding, positions past a prompt's end in its
bucket, the second bfloat16 term of every activation and the zeros that pad
v to 192 are time without required work: they lower it.  None for another
family.

A program run is in the slot by its start; its requests are those whose
first token left the engine between the slot's edges moved later by the
median prefill's length (the first token follows its prefill at once).
At most one request at each edge is matched wrongly."""

from benchmarks.harness import stats
from benchmarks.harness.loader import sibling_reader

LAYER = "model step"
UNIT = "%"
MOVES = "batch_tokens_per_s"
PROGRAMS = ("prefill_many", "prefill_one")

costs = sibling_reader(__file__, "mla_moe_costs")


def read(obs):
    trace, peak = obs.get("trace"), obs.get("peaks")
    if not trace or not peak or "window_mono_s" not in trace \
            or obs.get("family") != "mla_moe":
        return None
    runs = [d for p in PROGRAMS for d in trace["program_ns"].get(p, [])]
    if not runs:
        return None
    lag = stats.median(runs) / 1e9
    t0, t1 = (t + lag for t in trace["window_mono_s"])
    prompts = [s["prompt_len"] for s in obs.get("replica_spans", [])
               if s["first"] is not None and t0 <= s["first"] < t1]
    if not prompts:
        return None
    required = sum(costs.prefill_flops(obs["sizes"], n) for n in prompts)
    return 100.0 * required / (sum(runs) / 1e9 * peak["bf16_flops_per_s"])
