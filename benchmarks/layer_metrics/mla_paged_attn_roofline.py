"""kernels: the latent paged-attention kernel
(`ops/paged_attention.paged_latent_attention_batch`) against its roofline
in the `mla_moe` family's decode program.  The step's latent calls (one a
layer) are told from the grouped products by where they stand in a step
(`mla_moe_costs.split_kernel_calls`); their least time is the longer of
their latent bytes (each resident token's 1,152 bytes read ONCE, as key and
as value) and their operations (16 heads x (576 + 512) x 2 a token) by
`mla_moe_costs.latent_kernel_cost`, at the tokens resident over the traced
slot; over THEIR device time.  The row the program pads to 640 values is
bytes the algorithm does not need: it lowers the share.  None for another
family."""

from benchmarks.harness import kernel_costs
from benchmarks.harness.loader import sibling_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "batch_tokens_per_s"
PROGRAM = "decode_chunk_paged"

costs = sibling_reader(__file__, "mla_moe_costs")


def read(obs):
    trace, peak = obs.get("trace"), obs.get("peaks")
    if not trace or not peak or "window_mono_s" not in trace \
            or obs.get("family") != "mla_moe":
        return None
    calls = trace["kernel_ns"].get(PROGRAM, [])
    t0, t1 = trace["window_mono_s"]
    resident = [s[4] for s in obs.get("samples", []) if t0 <= s[0] <= t1]
    latent = costs.split_kernel_calls(calls, obs["sizes"])["latent"]
    if not latent or not resident:
        return None
    least = kernel_costs.roofline_seconds(*costs.latent_kernel_cost(
        obs["sizes"], obs["max_batch"], sum(resident) / len(resident)),
        peak)[0]
    return 100.0 * least * len(latent) / (sum(latent) / 1e9)
