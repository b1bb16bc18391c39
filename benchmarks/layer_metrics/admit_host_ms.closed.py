"""engine: the host's own time in an admission, as `admit_host_ms` reads it, for closed-loop cells: what
their users feel is the work completed, so here it moves
`batch_tokens_per_s`."""

from benchmarks.harness.loader import sibling_reader

LAYER = "engine"
UNIT = "ms"
MOVES = "batch_tokens_per_s"

read = sibling_reader(__file__, "admit_host_ms").read
