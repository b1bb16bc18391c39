"""engine: pages the decode kernel visits over pages the tables hold, as `paged_live_share` reads it, for closed-loop cells: what
their users feel is the work completed, so here it moves
`batch_tokens_per_s`."""

from benchmarks.harness.loader import sibling_reader

LAYER = "engine"
UNIT = "%"
MOVES = "batch_tokens_per_s"

read = sibling_reader(__file__, "paged_live_share").read
