"""engine: the share of a decode step's (row, expert) pairs whose expert
this chip holds.  100 x sum `expert_pairs_held` / sum `expert_pairs` over
the `engine.decode.wait` spans of the window (each chunk's own counts ride
on its span: live rows x experts a token x layers in the denominator).  50
under even routing where two chips share a layer; what uneven routing, or
another division of the experts, will move: the rest is the other chip's
work, and the step is as long as the fuller chip's.  Lower is less work
here.  None on a program that counts nothing."""

from benchmarks.harness.loader import sibling_reader

LAYER = "engine"
UNIT = "%"
MOVES = "batch_tokens_per_s"

program_spans = sibling_reader(__file__, "program_spans")


def read(obs):
    spans = program_spans.session(obs.get("window"))
    chunks = [r.get("attrs", {}) for r in spans.named("engine.decode.wait")] \
        if spans else []
    pairs = sum(a.get("expert_pairs", 0) for a in chunks)
    if not pairs:
        return None
    return 100.0 * sum(a.get("expert_pairs_held", 0) for a in chunks) / pairs
