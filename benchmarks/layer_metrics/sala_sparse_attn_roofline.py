"""kernels: the paged kernel as the `minicpm_sala` family's sparse layers
call it (`ops/paged_attention.paged_decode_attention_batch` over a table
GATHERED from the step's selection, a row of its batch a (sequence, K/V
head)) against its roofline in the decode program.  The program's only
custom calls are these, one a sparse layer and step; their least time is
the longer of the selected pages' bytes (K and V of each kept page once,
the page each table writes, the float32 queries and outputs) and their
operations, by `minicpm_sala_costs.sparse_kernel_cost` at the program's own
count of pages read over the traced slot (`sparse_pages_read`); over THEIR
device time.  A page's dead tokens and the dead columns of a gathered table
are time without required work.  None for another family."""

from benchmarks.harness import kernel_costs
from benchmarks.harness.loader import sibling_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "batch_tokens_per_s"
PROGRAM = "decode_chunk_paged"

costs = sibling_reader(__file__, "minicpm_sala_costs")
decode = sibling_reader(__file__, "sala_decode_roofline")


def read(obs):
    trace, peak = obs.get("trace"), obs.get("peaks")
    if not trace or not peak or "window_mono_s" not in trace \
            or obs.get("family") != "minicpm_sala":
        return None
    calls = trace["kernel_ns"].get(PROGRAM, [])
    t0, t1 = trace["window_mono_s"]
    inside = [s for s in obs.get("samples", []) if t0 <= s[0] <= t1]
    chunk = obs["config"]["serve"]["engine"]["decode_chunk"]
    counts = decode.counts_per_step(obs, chunk)
    if not calls or not inside or counts is None:
        return None
    sizes = obs["sizes"]
    live = sum(s[3] for s in inside) / len(inside)
    layers = costs.layers(sizes)["sparse"]
    # a step's calls together, times the steps the calls seen make up
    step = kernel_costs.roofline_seconds(*costs.sparse_kernel_cost(
        sizes, live * layers, counts["sparse_pages_read"],
        live * decode.tables_of(sizes)), peak)[0]
    return 100.0 * step * (len(calls) / layers) / (sum(calls) / 1e9)
