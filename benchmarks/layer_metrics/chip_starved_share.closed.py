"""engine: the share of the window in which the chip had nothing queued, as `chip_starved_share` reads it, for closed-loop cells: what
their users feel is the work completed, so here it moves
`batch_tokens_per_s`."""

from benchmarks.harness.loader import sibling_reader

LAYER = "engine"
UNIT = "%"
MOVES = "batch_tokens_per_s"

read = sibling_reader(__file__, "chip_starved_share").read
