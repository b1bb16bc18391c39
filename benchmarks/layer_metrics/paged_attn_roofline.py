"""kernels: the paged decode-attention kernel against its roofline.  The
kernel is every custom call inside the decode program.  Its least time is
max(ops / peak FLOP/s, bytes / peak bytes/s) for the tokens resident in
the cache (sampled in the replica over the traced window), from
`kernel_costs.paged_decode_cost`; at these sizes memory bounds it."""

from benchmarks.harness import kernel_costs

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
PROGRAM = "decode_chunk_paged"


def read(obs):
    trace, peak = obs.get("trace"), obs.get("peaks")
    if not trace or not peak or "window_mono_s" not in trace:
        return None
    calls = trace["kernel_ns"].get(PROGRAM, [])
    t0, t1 = trace["window_mono_s"]
    resident = [s[4] for s in obs.get("samples", []) if t0 <= s[0] <= t1]
    if not calls or not resident:
        return None
    flops, nbytes = kernel_costs.paged_decode_cost(
        obs["sizes"], obs["max_batch"], sum(resident) / len(resident))
    least, _bound = kernel_costs.roofline_seconds(flops, nbytes, peak)
    return 100.0 * least * len(calls) / (sum(calls) / 1e9)
