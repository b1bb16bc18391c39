"""model step: the operations the prompts admitted in the traced slot
REQUIRE of this chip (`granite_moe_hybrid_costs.prefill_flops`: mixers,
shared experts and routers at every position, the causal half of attention
at heads of 128, the conv and the recurrence, the head at the last token
only; and `pair_flops` for every (row, expert) pair whose expert is HELD
here, by the program's own count, `expert_rows` on the slot's
`engine.prefill.wait` spans) over the device time of the prefill programs
that ran in it times the chip's peak: `prefill_mfu.py`'s reader with this
family's costs, the cell's share of the whole step's peak.  Rows of padding,
positions past a prompt's end in its bucket, the second bfloat16 term of
every activation and the chunked form's masked matrices are time without
required work: they lower it.  None for another family, and on a program
that counts nothing.

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

decode = sibling_reader(__file__, "gmh_decode_roofline")
costs = decode.costs


def held_pairs(obs):
    """(Row, expert) pairs in held groups over the prefills that started in
    the traced slot; None where the program counted none."""
    spans = decode.program_spans.session(
        decode.program_spans.traced_slot(obs))
    counted = [r["attrs"]["expert_rows"] for name in
               ("engine.prefill.wait", "engine.prefill")
               for r in (spans.named(name) if spans else [])
               if "expert_rows" in r.get("attrs", {})]
    return sum(counted) if counted else None


def read(obs):
    trace, peak = obs.get("trace"), obs.get("peaks")
    if not trace or not peak or "window_mono_s" not in trace \
            or obs.get("family") != decode.FAMILY:
        return None
    runs = [d for p in PROGRAMS for d in trace["program_ns"].get(p, [])]
    pairs = held_pairs(obs)
    if not runs or pairs is None:
        return None
    lag = stats.median(runs) / 1e9
    t0, t1 = (t + lag for t in trace["window_mono_s"])
    prompts = [s["prompt_len"] for s in obs.get("replica_spans", [])
               if s["first"] is not None and t0 <= s["first"] < t1]
    if not prompts:
        return None
    required = sum(costs.prefill_flops(obs["sizes"], n) for n in prompts) \
        + pairs * costs.pair_flops(obs["sizes"])
    return 100.0 * required / (sum(runs) / 1e9 * peak["bf16_flops_per_s"])
