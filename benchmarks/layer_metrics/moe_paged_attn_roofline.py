"""kernels: the paged decode-attention kernel against its roofline in the
`lfm2_moe` family's decode program, whose custom calls are mostly grouped
products: `paged_attn_roofline` holds EVERY custom call of the program to
the attention's least time and is not this cell's.  Here the step's
paged-attention calls (one an attention layer) are told from the grouped
products by where they stand in a step
(`lfm2_moe_costs.split_kernel_calls`); their least time is
`lfm2_moe_costs.paged_decode_cost` at the tokens resident over the traced
slot (reads only, as `kernel_costs.paged_decode_cost`).  None for another
family."""

from benchmarks.harness import kernel_costs
from benchmarks.harness.loader import sibling_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "batch_tokens_per_s"
PROGRAM = "decode_chunk_paged"

costs = sibling_reader(__file__, "lfm2_moe_costs")


def read(obs):
    trace, peak = obs.get("trace"), obs.get("peaks")
    if not trace or not peak or "window_mono_s" not in trace \
            or obs.get("family") != "lfm2_moe":
        return None
    calls = trace["kernel_ns"].get(PROGRAM, [])
    t0, t1 = trace["window_mono_s"]
    resident = [s[4] for s in obs.get("samples", []) if t0 <= s[0] <= t1]
    paged = costs.split_kernel_calls(calls, obs["sizes"])["paged"]
    if not paged or not resident:
        return None
    least = kernel_costs.roofline_seconds(*costs.paged_decode_cost(
        obs["sizes"], obs["max_batch"], sum(resident) / len(resident)),
        peak)[0]
    return 100.0 * least * len(paged) / (sum(paged) / 1e9)
