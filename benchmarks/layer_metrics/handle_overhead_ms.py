"""serve: what the handle, the router and the pulled stream add to the time
to first token.  Per request, client (sent -> first token received) minus
replica (`engine.submit` -> first token yielded); the median."""

from benchmarks.harness import stats

LAYER = "serve"
UNIT = "ms"
# The pulled stream hands over a whole decode chunk, so this is one chunk
# time, which `tpot_p90_ms` is too; the client's TTFT itself is recorded
# per layer (`client_ttft_p90_ms`), too noisy to be judged end to end.
MOVES = "tpot_p90_ms"


def read(obs):
    replica = {s["rid"]: s for s in obs.get("replica_spans", [])
               if s["first"] is not None}
    over = [(c["first"] - c["sent"]) - (replica[c["rid"]]["first"]
                                        - replica[c["rid"]]["submit"])
            for c in obs.get("client_spans", [])
            if c["first"] is not None and c["rid"] in replica]
    return stats.median(over) * 1e3 if over else None
