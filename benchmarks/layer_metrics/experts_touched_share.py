"""engine: the share of the routed layers' experts that a decode step's
live rows chose.  100 x sum `experts_touched` / sum `expert_slots` over the
`engine.decode.wait` spans of the window (each chunk's own counts ride on
its span: steps x routed layers x experts in the denominator): which
regime the cell was in (16 rows of top-4 of 64 touch about 60%), and what a
later change to batching or routing moved.  Lower is fewer bytes a step.
None on a program that counts nothing."""

from benchmarks.harness.loader import sibling_reader

LAYER = "engine"
UNIT = "%"
MOVES = "batch_tokens_per_s"

program_spans = sibling_reader(__file__, "program_spans")


def read(obs):
    spans = program_spans.session(obs.get("window"))
    chunks = [r.get("attrs", {}) for r in spans.named("engine.decode.wait")] \
        if spans else []
    slots = sum(a.get("expert_slots", 0) for a in chunks)
    if not slots:
        return None
    return 100.0 * sum(a.get("experts_touched", 0) for a in chunks) / slots
