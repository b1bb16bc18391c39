"""kernels: decode attention over the ONE paged K/V layer that the full
layer and every cross-attention layer read, against its roofline.  The
kernel is every custom call inside the decode program
(`trace["kernel_ns"]` lumps a program's kernels; here they are all the
paged kernel, one call for each reading layer and step).  Its least time
is max(ops / peak FLOP/s, bytes / peak bytes/s) for the tokens resident in
the pool (sampled in the replica over the traced window), from
`sambay_costs.shared_kv_decode_cost`; memory bounds it.  The window
layers' rings are read by plain XLA operations in this program, so no
custom call's time holds them and their bytes are NOT in the least time
(`sambay_costs.ring_bytes_per_step` says what they are)."""

from benchmarks.harness import kernel_costs
from benchmarks.harness.loader import sibling_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "batch_tokens_per_s"
PROGRAM = "decode_chunk_paged"

costs = sibling_reader(__file__, "sambay_costs")


def read(obs):
    trace, peak = obs.get("trace"), obs.get("peaks")
    if not trace or not peak or "window_mono_s" not in trace \
            or obs.get("family") != "sambay":
        return None
    calls = trace["kernel_ns"].get(PROGRAM, [])
    t0, t1 = trace["window_mono_s"]
    resident = [s[4] for s in obs.get("samples", []) if t0 <= s[0] <= t1]
    if not calls or not resident:
        return None
    flops, nbytes = costs.shared_kv_decode_cost(
        obs["sizes"], obs["max_batch"], sum(resident) / len(resident))
    least, _bound = kernel_costs.roofline_seconds(flops, nbytes, peak)
    return 100.0 * least * len(calls) / (sum(calls) / 1e9)
