"""model step: one decode step on the device, as `decode_step_ms` reads it, for closed-loop cells: what
their users feel is the work completed, so here it moves
`batch_tokens_per_s`."""

from benchmarks.harness.loader import sibling_reader

LAYER = "model step"
UNIT = "ms"
MOVES = "batch_tokens_per_s"

read = sibling_reader(__file__, "decode_step_ms").read
