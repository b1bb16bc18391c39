"""model step: one whole decode step of the `minicpm_sala` family against
its roofline.  Least time of a step, max(ops / peak FLOP/s, bytes / peak
bytes/s) by `minicpm_sala_costs.decode_step_cost`: every weight but the
embedding once, each live row's SELECTED pages and the page it writes, the
compressed keys its sparse rows can see, its lightning state read and
written once, by the program's own counts (`sparse_pages_read`,
`compressed_keys_read` on the `engine.decode.wait` spans of the traced
slot, a step's mean) at the streams that were decoding as the replica
sampled them; over the median device time of the decode program
(`decode_chunk_paged`) divided by the steps of a chunk.  None for another
family, and on a program that counts nothing."""

from benchmarks.harness import kernel_costs, stats
from benchmarks.harness.loader import sibling_reader

LAYER = "model step"
UNIT = "%"
MOVES = "batch_tokens_per_s"
PROGRAM = "decode_chunk_paged"

costs = sibling_reader(__file__, "minicpm_sala_costs")
program_spans = sibling_reader(__file__, "program_spans")


def counts_per_step(obs, chunk: int):
    """The family's step counters over the traced slot, a step's mean
    (name -> value), or None where no chunk carries them."""
    spans = program_spans.session(program_spans.traced_slot(obs))
    chunks = [r.get("attrs", {}) for r in spans.named("engine.decode.wait")] \
        if spans else []
    chunks = [a for a in chunks if "sparse_pages_read" in a]
    if not chunks:
        return None
    steps = len(chunks) * chunk
    return {name: sum(a[name] for a in chunks) / steps
            for name in ("sparse_pages_read", "sparse_pages_resident",
                         "sparse_rows", "compressed_keys_read")}


def tables_of(sizes: dict) -> int:
    return costs.layers(sizes)["sparse"] * sizes["num_key_value_heads"]


def read(obs):
    trace, peak = obs.get("trace"), obs.get("peaks")
    if not trace or not peak or "window_mono_s" not in trace \
            or obs.get("family") != "minicpm_sala":
        return None
    runs = trace["program_ns"].get(PROGRAM, [])
    t0, t1 = trace["window_mono_s"]
    inside = [s for s in obs.get("samples", []) if t0 <= s[0] <= t1]
    chunk = obs["config"]["serve"]["engine"]["decode_chunk"]
    counts = counts_per_step(obs, chunk)
    if not runs or not inside or counts is None:
        return None
    live = sum(s[3] for s in inside) / len(inside)
    flops, nbytes = costs.decode_step_cost(
        obs["sizes"], live, counts["sparse_pages_read"],
        counts["compressed_keys_read"], live * tables_of(obs["sizes"]))
    least, _bound = kernel_costs.roofline_seconds(flops, nbytes, peak)
    return 100.0 * least / (stats.median(runs) / 1e9 / chunk)
