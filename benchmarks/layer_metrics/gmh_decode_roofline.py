"""model step: one whole decode step of the `granite_moe_hybrid` family
against its roofline.  Least time of a step, max(ops / peak FLOP/s, bytes /
peak bytes/s) by `granite_moe_hybrid_costs.decode_step_cost`: every weight
that is no routed expert's once, each HELD expert that the step's live rows
touched once and each held (row, expert) pair's operations (the program's
own counts, `experts_touched` and `expert_pairs_held` on the
`engine.decode.wait` spans of the traced slot, a step's mean), each live
slot's Mamba-2 state read and written once and each resident token's K and
V once, at the streams that are decoding and their resident tokens as the
replica sampled them over the slot; over the median device time of the
decode program (`decode_chunk_paged`) divided by the steps of a chunk.
Memory bounds it.  None for another family, and on a program that counts
nothing."""

from benchmarks.harness import kernel_costs, stats
from benchmarks.harness.loader import sibling_reader

LAYER = "model step"
UNIT = "%"
MOVES = "batch_tokens_per_s"
PROGRAM = "decode_chunk_paged"
FAMILY = "granite_moe_hybrid"

costs = sibling_reader(__file__, "granite_moe_hybrid_costs")
program_spans = sibling_reader(__file__, "program_spans")


def counted_per_step(obs, chunk: int):
    """(held experts touched, held pairs) in a decode step of the traced
    slot, summed over the layers: the chunks' counts over their steps."""
    spans = program_spans.session(program_spans.traced_slot(obs))
    chunks = [r.get("attrs", {}) for r in spans.named("engine.decode.wait")] \
        if spans else []
    chunks = [a for a in chunks if "expert_pairs_held" in a]
    if not chunks:
        return None
    steps = len(chunks) * chunk
    return (sum(a["experts_touched"] for a in chunks) / steps,
            sum(a["expert_pairs_held"] for a in chunks) / steps)


def read(obs):
    trace, peak = obs.get("trace"), obs.get("peaks")
    if not trace or not peak or "window_mono_s" not in trace \
            or obs.get("family") != FAMILY:
        return None
    runs = trace["program_ns"].get(PROGRAM, [])
    t0, t1 = trace["window_mono_s"]
    inside = [s for s in obs.get("samples", []) if t0 <= s[0] <= t1]
    chunk = obs["config"]["serve"]["engine"]["decode_chunk"]
    counted = counted_per_step(obs, chunk)
    if not runs or not inside or counted is None:
        return None
    live = sum(s[3] for s in inside) / len(inside)
    resident = sum(s[4] for s in inside) / len(inside)
    flops, nbytes = costs.decode_step_cost(obs["sizes"], live, resident,
                                           *counted)
    least, _bound = kernel_costs.roofline_seconds(flops, nbytes, peak)
    return 100.0 * least / (stats.median(runs) / 1e9 / chunk)
