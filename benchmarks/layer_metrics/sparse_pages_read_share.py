"""engine: the share of the resident pages of the sparse layers that the
decode steps of the window READ.  100 x `sparse_pages_read` /
`sparse_pages_resident`, both summed over the `engine.decode.wait` spans of
the window (a chunk's span carries them summed over its steps, its live
rows and the (sparse layer, K/V head) tables).  Which regime the cell is
in: 100 while every stream is under `dense_len`, 97 / (length / 64) for a
stream past it (a fifth at 32k tokens).  None on a program that counts no
selected pages."""

from benchmarks.harness.loader import sibling_reader

LAYER = "engine"
UNIT = "%"
MOVES = "batch_tokens_per_s"

program_spans = sibling_reader(__file__, "program_spans")


def read(obs):
    spans = program_spans.session(obs.get("window"))
    chunks = [r.get("attrs", {}) for r in spans.named("engine.decode.wait")] \
        if spans else []
    resident = sum(a.get("sparse_pages_resident", 0) for a in chunks)
    if not resident:
        return None
    return 100.0 * sum(a["sparse_pages_read"] for a in chunks
                       if "sparse_pages_read" in a) / resident
