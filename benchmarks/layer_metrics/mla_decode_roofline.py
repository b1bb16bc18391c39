"""model step: one whole decode step of the `mla_moe` family against its
roofline.  Least time of a step, max(ops / peak FLOP/s, bytes / peak
bytes/s) by `mla_moe_costs.decode_step_cost`: every weight that is no
routed expert's once (the shared experts and the head among them), each
expert that the step's live rows TOUCHED once (the program's own count,
`experts_touched` on the `engine.decode.wait` spans of the traced slot, a
step's mean), each resident token's 1,152 bytes of latent a layer once, at
the streams that are decoding and their resident tokens as the replica
sampled them over the slot; over the median device time of the decode
program (`decode_chunk_paged`) divided by the steps of a chunk.  None for
another family, and on a program that counts nothing."""

from benchmarks.harness import kernel_costs, stats
from benchmarks.harness.loader import sibling_reader

LAYER = "model step"
UNIT = "%"
MOVES = "batch_tokens_per_s"
PROGRAM = "decode_chunk_paged"

costs = sibling_reader(__file__, "mla_moe_costs")
# (the same count on the same span, whichever routed family wrote it)
touched_per_step = sibling_reader(
    __file__, "moe_decode_roofline").touched_per_step


def read(obs):
    trace, peak = obs.get("trace"), obs.get("peaks")
    if not trace or not peak or "window_mono_s" not in trace \
            or obs.get("family") != "mla_moe":
        return None
    runs = trace["program_ns"].get(PROGRAM, [])
    t0, t1 = trace["window_mono_s"]
    inside = [s for s in obs.get("samples", []) if t0 <= s[0] <= t1]
    chunk = obs["config"]["serve"]["engine"]["decode_chunk"]
    touched = touched_per_step(obs, chunk)
    if not runs or not inside or touched is None:
        return None
    live = sum(s[3] for s in inside) / len(inside)
    resident = sum(s[4] for s in inside) / len(inside)
    flops, nbytes = costs.decode_step_cost(obs["sizes"], live, resident,
                                           touched)
    least, _bound = kernel_costs.roofline_seconds(flops, nbytes, peak)
    return 100.0 * least / (stats.median(runs) / 1e9 / chunk)
