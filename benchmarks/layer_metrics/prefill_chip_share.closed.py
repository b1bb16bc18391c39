"""model step: the share of the window the chip spent on prefill programs, as `prefill_chip_share` reads it, for closed-loop cells: what
their users feel is the work completed, so here it moves
`batch_tokens_per_s`."""

from benchmarks.harness.loader import sibling_reader

LAYER = "model step"
UNIT = "%"
MOVES = "batch_tokens_per_s"

read = sibling_reader(__file__, "prefill_chip_share").read
