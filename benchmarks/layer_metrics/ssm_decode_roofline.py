"""model step: one whole decode step of the `granite_hybrid` family against
its roofline.  Least time of a step, max(ops / peak FLOP/s, bytes / peak
bytes/s) by `granite_hybrid_costs.decode_step_cost` (every weight once, each
live slot's recurrent state read and written once, each resident token's K
and V once) at the streams that are decoding (a slot that awaits its
prefill is not one) and their resident tokens, sampled in the replica over
the traced slot, over the median device time of the decode program
(`decode_chunk_paged`) divided by the steps of a chunk.  Memory bounds it:
this is the share of the whole step's roofline that a later change to this
cell's decode is held under.  None for another family."""

from benchmarks.harness import kernel_costs, stats
from benchmarks.harness.loader import sibling_reader

LAYER = "model step"
UNIT = "%"
MOVES = "batch_tokens_per_s"
PROGRAM = "decode_chunk_paged"

costs = sibling_reader(__file__, "granite_hybrid_costs")


def read(obs):
    trace, peak = obs.get("trace"), obs.get("peaks")
    if not trace or not peak or "window_mono_s" not in trace \
            or obs.get("family") != "granite_hybrid":
        return None
    runs = trace["program_ns"].get(PROGRAM, [])
    t0, t1 = trace["window_mono_s"]
    inside = [s for s in obs.get("samples", []) if t0 <= s[0] <= t1]
    if not runs or not inside:
        return None
    live = sum(s[3] for s in inside) / len(inside)
    resident = sum(s[4] for s in inside) / len(inside)
    flops, nbytes = costs.decode_step_cost(obs["sizes"], live, resident)
    least, _bound = kernel_costs.roofline_seconds(flops, nbytes, peak)
    chunk = obs["config"]["serve"]["engine"]["decode_chunk"]
    return 100.0 * least / (stats.median(runs) / 1e9 / chunk)
