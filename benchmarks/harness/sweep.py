"""The knee sweep of an open-loop cell: one replica, each rate for
`seconds`, one table.  Run once when the cell is defined (and again by a
`benchmark` issue after an optimisation has moved the knee); the cell
itself runs at a fixed rate, about four fifths of the knee."""

from __future__ import annotations

import copy
import time

from benchmarks.harness import serve_common, stats, traffic
from benchmarks.harness.loader import BenchmarkError


def run(cell, seed, seconds, rates, t_start, platform, log) -> None:
    if cell.traffic["kind"] != "serve_open":
        raise BenchmarkError("the sweep is for open-loop cells")
    from benchmarks.drivers import serve_open

    table = []

    def schedule_all(ctx):
        threads = []
        ctx.requests = []
        for i, rate in enumerate(rates):
            mix = copy.deepcopy(cell.traffic)
            mix["rate_rps"] = rate
            t0 = time.monotonic()
            requests = traffic.serve_requests(
                mix, seed, cell.family.sizes(cell.config)["vocab_size"],
                seconds)
            for r in requests:       # rids stay unique over the rates
                r.rid += 100000 * (i + 1)
            ctx.requests.extend(requests)
            sub = serve_common.Context(ctx.handle, requests, t0, seconds,
                                       mix)
            mine = serve_open._schedule(sub)
            for t in mine:
                t.join(max(0.0, t0 + seconds + serve_common.DRAIN_S
                           - time.monotonic()))
            half = t0 + seconds / 2
            done = [s for s in sub.spans if s["error"] is None
                    and s["tokens"] == s["want"]]
            first = [stats.ttft_ms(s) for s in sub.spans
                     if s["first"] is not None and s["due"] < half]
            second = [stats.ttft_ms(s) for s in sub.spans
                      if s["first"] is not None and s["due"] >= half]
            tpot = [v for v in map(stats.tpot_ms, done) if v is not None]
            row = {"rate_rps": rate, "sent": len(sub.spans),
                   "completed": len(done),
                   "out_tokens_per_s": sum(s["tokens"] for s in done)
                   / seconds,
                   "ttft_p50_ms_first_half": stats.median(first)
                   if first else None,
                   "ttft_p50_ms_second_half": stats.median(second)
                   if second else None,
                   "ttft_p90_ms": stats.percentile(first + second, 90)
                   if first or second else None,
                   "tpot_p90_ms": stats.percentile(tpot, 90)
                   if tpot else None}
            table.append(row)
            log(phase="sweep", **row)
            ctx.spans.extend(sub.spans)
            threads.extend(mine)
        return threads

    serve_common.run(cell, seed, seconds, False, t_start, platform,
                     schedule_all, log)
    log(phase="sweep_table", rows=table)
