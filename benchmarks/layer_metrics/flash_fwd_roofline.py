"""kernels: the flash-attention forward kernel against its roofline.  The
kernel is every custom call inside the train step program (with
rematerialisation it runs more than once a layer; each call is held to
the same bound).  Least time of a call from
`kernel_costs.flash_forward_cost` (causal: the lower triangle); compute
bounds it at these sizes."""


from benchmarks.harness import kernel_costs
from benchmarks.harness.loader import sibling_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"


_step_program = sibling_reader(__file__, "train_step_device_ms").step_program


def read(obs):
    trace, peak = obs.get("trace"), obs.get("peaks")
    if not trace or not peak or "steps" not in obs:
        return None
    calls = trace["kernel_ns"].get(_step_program(trace), [])
    if not calls:
        return None
    flops, nbytes = kernel_costs.flash_forward_cost(
        obs["sizes"], obs["rows"], obs["seq"])
    least, _bound = kernel_costs.roofline_seconds(flops, nbytes, peak)
    return 100.0 * least * len(calls) / (sum(calls) / 1e9)
