"""kernels: the grouped matrix products of the routed layers (the Pallas
`megablox.gmm`, two calls a routed layer) against their roofline, in the
decode program.  A step's least time for them, by
`lfm2_moe_costs.grouped_product_cost`: the longer of its operations at
peak and of its bytes (each expert its live rows TOUCHED once, by the
program's own count over the traced slot, and each (row, expert) pair in
and out) at peak bandwidth; memory bounds it at 16 rows.  Over the device
time of THOSE calls: `trace["kernel_ns"]` holds every custom call of the
decode program in the order they ran and keeps no names, so the step's
two paged-attention calls are told from its sixteen grouped products by
where they stand in a step (`lfm2_moe_costs.split_kernel_calls`) and
left out.  None for another family, and on a program that counts
nothing."""

from benchmarks.harness import kernel_costs
from benchmarks.harness.loader import sibling_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "batch_tokens_per_s"
PROGRAM = "decode_chunk_paged"

costs = sibling_reader(__file__, "lfm2_moe_costs")
decode = sibling_reader(__file__, "moe_decode_roofline")


def read(obs):
    trace, peak = obs.get("trace"), obs.get("peaks")
    if not trace or not peak or "window_mono_s" not in trace \
            or obs.get("family") != "lfm2_moe":
        return None
    calls = trace["kernel_ns"].get(PROGRAM, [])
    t0, t1 = trace["window_mono_s"]
    inside = [s for s in obs.get("samples", []) if t0 <= s[0] <= t1]
    sizes = obs["sizes"]
    chunk = obs["config"]["serve"]["engine"]["decode_chunk"]
    touched = decode.touched_per_step(obs, chunk)
    if not calls or not inside or touched is None:
        return None
    live = sum(s[3] for s in inside) / len(inside)
    grouped = costs.split_kernel_calls(calls, sizes)["grouped"]
    if not grouped:
        return None
    least = kernel_costs.roofline_seconds(*costs.grouped_product_cost(
        sizes, live * sizes["num_experts_per_tok"]
        * costs.layers(sizes)["routed"], touched), peak)[0]
    steps = len(grouped) / costs.kernel_order(sizes).count("grouped")
    return 100.0 * least * steps / (sum(grouped) / 1e9)
