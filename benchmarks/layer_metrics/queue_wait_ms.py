"""engine: admission.  Median duration of the program's `request.queue`
spans that begin in the window: `engine.submit` -> a slot and the
request's pages reserved, taken where it happens (`LLMEngine._loop`)."""

from benchmarks.harness import stats
from benchmarks.harness.loader import sibling_reader

LAYER = "engine"
UNIT = "ms"
# A request that waits holds no slot: the streams that are decoding share
# the step with fewer others; when it is admitted its prefill stalls them.
MOVES = "tpot_p90_ms"

program_spans = sibling_reader(__file__, "program_spans")


def read(obs):
    spans = program_spans.session(obs.get("window"))
    waits = [r["dur_ns"] for r in spans.named("request.queue")] \
        if spans else []
    return stats.median(waits) / 1e6 if waits else None
