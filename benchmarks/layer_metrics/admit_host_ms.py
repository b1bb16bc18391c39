"""engine: the host's own time in one admission.  Median, over the
`engine.admit` spans of the traced slot that prefilled (hold an
`engine.prefill`), of the span less its `*.wait` descendants: candidate
gathering, page reservation, the host arrays of the prefill, its dispatch,
the commit."""

from benchmarks.harness import stats
from benchmarks.harness.loader import sibling_reader

LAYER = "engine"
UNIT = "ms"
MOVES = "tpot_p90_ms"

program_spans = sibling_reader(__file__, "program_spans")


def read(obs):
    spans = program_spans.session(program_spans.traced_slot(obs))
    if spans is None:
        return None
    host = [spans.host_only_ns(a) for a in spans.named("engine.admit")
            if any(d["name"] == "engine.prefill"
                   for d in spans.descendants(a))]
    return stats.median(host) / 1e6 if host else None
