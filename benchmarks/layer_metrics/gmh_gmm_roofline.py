"""kernels: the grouped matrix products of the `granite_moe_hybrid` family's
routed layers (`ops/grouped_matmul.py`, two calls a layer: W1|W3 with a
contraction of 4,096 into 1,536 columns, then W2 with 768 into 4,096; 36
groups, of a token's ten pairs those that lie in one) against their
roofline, in the decode program.  A step's least time for them, by
`granite_moe_hybrid_costs.grouped_product_cost`: the longer of its
operations at peak and of its bytes (each held expert its live rows
TOUCHED once and each HELD pair in and out, by the program's own counts
over the traced slot) at peak bandwidth; memory bounds it at 48 rows.  Over
the device time of THOSE calls: `trace["kernel_ns"]` keeps no names, so a
step's twenty grouped products are told from its one paged-attention call
by where they stand in a step (`granite_moe_hybrid_costs.split_kernel_calls`).
`moe_gmm_roofline` and `mla_gmm_roofline` read the same kernel in the other
routed cells, at other shapes.  None for another family, and on a program
that counts nothing."""

from benchmarks.harness import kernel_costs
from benchmarks.harness.loader import sibling_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "batch_tokens_per_s"
PROGRAM = "decode_chunk_paged"

decode = sibling_reader(__file__, "gmh_decode_roofline")
costs = decode.costs


def read(obs):
    trace, peak = obs.get("trace"), obs.get("peaks")
    if not trace or not peak or "window_mono_s" not in trace \
            or obs.get("family") != decode.FAMILY:
        return None
    calls = trace["kernel_ns"].get(PROGRAM, [])
    sizes = obs["sizes"]
    chunk = obs["config"]["serve"]["engine"]["decode_chunk"]
    counted = decode.counted_per_step(obs, chunk)
    grouped = costs.split_kernel_calls(calls, sizes)["grouped"]
    if not grouped or counted is None:
        return None
    touched, pairs = counted
    least = kernel_costs.roofline_seconds(
        *costs.grouped_product_cost(sizes, pairs, touched), peak)[0]
    order = costs.kernel_order(sizes)
    steps = len(grouped) / (len(order) - order.count("paged"))
    return 100.0 * least * steps / (sum(grouped) / 1e9)
