"""kernels: the grouped matrix products of the `mla_moe` family's routed
layers (the Pallas `megablox.gmm` through `ops/grouped_matmul.py`, two
calls a routed layer: W1|W3 with 2,816 columns in tiles of 256, then W2)
against their roofline, in the decode program.  A step's least time for
them, by `mla_moe_costs.grouped_product_cost`: the longer of its
operations at peak and of its bytes (each expert its live rows TOUCHED
once, by the program's own count over the traced slot, and each (row,
expert) pair in and out) at peak bandwidth; at 64 rows of six experts
memory bounds it.  Over the device time of THOSE calls: `kernel_ns` keeps
no names, so a step's twelve grouped products are told from its seven
latent calls by where they stand in a step
(`mla_moe_costs.split_kernel_calls`).  The shared experts are plain
products, not grouped ones, and are not in it.  `moe_gmm_roofline` reads
the same kernel in `lfm2_moe`'s cell, at other shapes and tiles.  None for
another family, and on a program that counts nothing."""

from benchmarks.harness import kernel_costs
from benchmarks.harness.loader import sibling_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "batch_tokens_per_s"
PROGRAM = "decode_chunk_paged"

costs = sibling_reader(__file__, "mla_moe_costs")
touched_per_step = sibling_reader(
    __file__, "moe_decode_roofline").touched_per_step


def read(obs):
    trace, peak = obs.get("trace"), obs.get("peaks")
    if not trace or not peak or "window_mono_s" not in trace \
            or obs.get("family") != "mla_moe":
        return None
    calls = trace["kernel_ns"].get(PROGRAM, [])
    t0, t1 = trace["window_mono_s"]
    inside = [s for s in obs.get("samples", []) if t0 <= s[0] <= t1]
    sizes = obs["sizes"]
    chunk = obs["config"]["serve"]["engine"]["decode_chunk"]
    touched = touched_per_step(obs, chunk)
    grouped = costs.split_kernel_calls(calls, sizes)["grouped"]
    if not grouped or not inside or touched is None:
        return None
    live = sum(s[3] for s in inside) / len(inside)
    least = kernel_costs.roofline_seconds(*costs.grouped_product_cost(
        sizes, live * sizes["num_experts_per_tok"]
        * costs.layers(sizes)["routed"], touched), peak)[0]
    steps = len(grouped) / costs.kernel_order(sizes).count("grouped")
    return 100.0 * least * steps / (sum(grouped) / 1e9)
