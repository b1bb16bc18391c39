"""engine: the host's own time in one pass of the loop.  Median, over the
`engine.pass` spans of the traced slot that hold an `engine.decode.wait`,
of the pass less its `*.wait` descendants: build, dispatch, the token walk,
admission outside the device waits.  While it runs the device has nothing
queued: it is what `device_idle` is made of."""

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
    host = [spans.host_only_ns(p) for p in spans.named("engine.pass")
            if any(d["name"] == "engine.decode.wait"
                   for d in spans.descendants(p))]
    return stats.median(host) / 1e6 if host else None
