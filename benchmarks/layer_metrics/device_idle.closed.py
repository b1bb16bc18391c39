"""device: share of the traced window with no operation on the chip, as `device_idle.serve` reads it, for closed-loop cells: what
their users feel is the work completed, so here it moves
`batch_tokens_per_s`."""

from benchmarks.harness.loader import sibling_reader

LAYER = "device"
UNIT = "%"
MOVES = "batch_tokens_per_s"

read = sibling_reader(__file__, "device_idle.serve").read
