"""Closed loop: `clients` callers that each wait for a reply and send their
next request when the last completes (a task pool or `map_batches` calling
a Serve handle with bounded concurrency).  A slower system is offered
less; what is judged is the work it completes in the window."""

from __future__ import annotations

import threading
import time

from benchmarks.harness import serve_common


def _schedule(ctx) -> list:
    clients = int(ctx.traffic["clients"])
    t_end = ctx.t0 + ctx.seconds

    def client(k):
        mine = ctx.requests[k::clients]
        for req in mine:
            now = time.monotonic()
            if now >= t_end:
                return
            ctx.send(req, now)

    threads = [threading.Thread(target=client, args=(k,), daemon=True)
               for k in range(clients)]
    for t in threads:
        t.start()
    return threads


def run(cell, seed, seconds, trace, t_start, platform, log) -> dict:
    return serve_common.run(cell, seed, seconds, trace, t_start, platform,
                            _schedule, log)
