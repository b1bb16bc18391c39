"""serve: the tail of the time to first token as the client sees it, from
when a request was DUE to its first token through the handle; the 90th
percentile over all the window's requests.  It was an end-to-end metric
until the driver's check read it spreading 6-7% from run to run (a wait of
up to one decode chunk before admission and one more before the pulled
stream hands over, over 108 requests: PERF.md, section 2); recorded here,
unjudged, beside the end-to-end metric that the same chunk time moves."""

from benchmarks.harness import stats

LAYER = "serve"
UNIT = "ms"
MOVES = "tpot_p90_ms"


def read(obs):
    ttft = [stats.ttft_ms(s) for s in obs.get("client_spans", [])
            if s["first"] is not None]
    return stats.percentile(ttft, 90) if ttft else None
