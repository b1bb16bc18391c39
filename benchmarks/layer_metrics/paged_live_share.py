"""engine: pages the decode kernel visits over pages the tables hold.
100 x sum `pages_live` / sum `pages_table` over the `engine.decode.wait`
spans of the window: each chunk's own counts ride on its span, so the
share is the window's and leaves warm-up out."""

from benchmarks.harness.loader import sibling_reader

LAYER = "engine"
UNIT = "%"
MOVES = "tpot_p90_ms"

program_spans = sibling_reader(__file__, "program_spans")


def read(obs):
    spans = program_spans.session(obs.get("window"))
    chunks = [r.get("attrs", {}) for r in spans.named("engine.decode.wait")] \
        if spans else []
    table = sum(a.get("pages_table", 0) for a in chunks)
    if not table:
        return None
    return 100.0 * sum(a.get("pages_live", 0) for a in chunks) / table
