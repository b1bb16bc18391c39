"""model step: what a thousand prompt tokens cost the chip, as `prefill_chip_ms_per_ktok` reads it, for closed-loop cells: what
their users feel is the work completed, so here it moves
`batch_tokens_per_s`."""

from benchmarks.harness.loader import sibling_reader

LAYER = "model step"
UNIT = "ms/ktok"
MOVES = "batch_tokens_per_s"

read = sibling_reader(__file__, "prefill_chip_ms_per_ktok").read
