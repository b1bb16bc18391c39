"""engine: a request's wait for a slot and its pages, as `queue_wait_ms` reads it, for closed-loop cells: what
their users feel is the work completed, so here it moves
`batch_tokens_per_s`."""

from benchmarks.harness.loader import sibling_reader

LAYER = "engine"
UNIT = "ms"
MOVES = "batch_tokens_per_s"

read = sibling_reader(__file__, "queue_wait_ms").read
