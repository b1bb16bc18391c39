"""Open loop: independent users.  Requests are sent at their due times
whether or not earlier ones have finished, and latency counts from when a
request was DUE, so a stall is charged to every request it delays."""

from __future__ import annotations

import threading
import time

from benchmarks.harness import serve_common


def _schedule(ctx) -> list:
    threads = []

    def arrivals():
        for req in ctx.requests:
            due = ctx.t0 + req.due_s
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            t = threading.Thread(target=ctx.send, args=(req, due),
                                 daemon=True)
            threads.append(t)
            t.start()

    gen = threading.Thread(target=arrivals, daemon=True)
    gen.start()
    gen.join(ctx.seconds + 5.0)
    return threads


def run(cell, seed, seconds, trace, t_start, platform, log) -> dict:
    return serve_common.run(cell, seed, seconds, trace, t_start, platform,
                            _schedule, log)
