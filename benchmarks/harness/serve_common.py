"""What the two serve drivers share: bring a TPU-leased `BenchLLM` replica
up through `serve.run`, warm the cell's own shapes, run the load through
the Serve handle from this process (which never touches a JAX backend),
drain, check against the plain reference, let the chip go.

A driver supplies only its loop: `schedule(ctx)` starts the requests of the
window (open loop: at their due times; closed loop: each client's next
when its last completes) and returns the threads to wait for.
"""

from __future__ import annotations

import os
import threading
import time

from benchmarks.harness import cluster, kernel_costs, stats, traffic
from benchmarks.harness.loader import BenchmarkError, Cell

REPLICA_READY_DEADLINE_S = 900.0
CALL_DEADLINE_S = 900.0
# A request may be due as the window closes and still be owed 384 tokens:
# 36 s at the usual ~95 ms a token, 55 s plus its wait in the one run in
# forty where the host stalled and a token took 144 ms (PERF.md, section 6).
# Only a request that is stuck, not one that is slow, counts as failed.  A
# run whose requests all finish never waits this long.
DRAIN_S = 120.0
WARMUP_NEW_TOKENS = 2          # first token + one decode chunk
REFERENCE_SAMPLES = 4
# Engine in bf16 against the reference in float32: how far the reference's
# logit of the engine's token may lie under the reference's own best.  Four
# bf16 steps at |logit| in [4, 8) (4 x 2^-5), where the top logits of these
# vocabularies lie.  Measured over every request of six runs (63,000
# positions, four seeds, both serve cells; PERF.md, section 6): largest
# 0.0535, one position in 2,000 over one step; the first form's two steps
# (0.0625, chip_smoke.py's) would refuse about one check in twenty-five on
# that tail alone.  A wrong token reads far more: the reference's own
# second-best lies 0.10-0.85 under its best (median over positions).
LOGIT_TIE_TOLERANCE = 0.125
# A position is set aside only by the reference itself (see
# `replica.check_reference`); of a sample's positions at most this share
# may be, else the sample fails: a context the served type cannot decide
# at one position in ten is no sample of a deployment.
SET_ASIDE_SHARE = 0.1
WIDE_REFERENCE_ENV = "BENCH_WIDE_REFERENCE"
DIAGNOSE_ENV = "BENCH_DIAGNOSE"


class Context:
    """What a driver's schedule needs."""

    def __init__(self, handle, requests, t0, seconds, traffic_params):
        self.handle = handle
        self.requests = requests
        self.t0 = t0
        self.seconds = seconds
        self.traffic = traffic_params
        self.spans: list = []
        self._lock = threading.Lock()

    def send(self, req, due: float) -> dict:
        """One request through the Serve handle, streamed; blocks until its
        last token.  Times are this process's `time.monotonic()`."""
        span = {"rid": req.rid, "due": due, "sent": time.monotonic(),
                "first": None, "last": None, "tokens": 0,
                "want": req.max_new_tokens, "prompt_len":
                len(req.prompt_tokens), "token_times": [], "error": None,
                "output": []}
        with self._lock:
            self.spans.append(span)
        try:
            gen = self.handle.options(stream=True).remote(
                {"rid": req.rid, "prompt_tokens": req.prompt_tokens,
                 "max_new_tokens": req.max_new_tokens})
            for tok in gen:
                now = time.monotonic()
                if span["first"] is None:
                    span["first"] = now
                span["last"] = now
                span["tokens"] += 1
                span["token_times"].append(now)
                span["output"].append(int(tok))
        except Exception as e:  # noqa: BLE001 -- a failed request is a result
            span["error"] = repr(e)
        span["done"] = time.monotonic()
        return span


def _call(handle, method, *args, deadline=CALL_DEADLINE_S):
    return handle.options(method_name=method).remote(*args).result(
        timeout=deadline)


def _trace_slot(seconds: float) -> tuple:
    length = min(8.0, max(1.0, seconds / 4))
    return 0.35 * seconds, 0.35 * seconds + length


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        platform: str, schedule, log) -> dict:
    from ray_tpu import serve

    sizes = cell.family.sizes(cell.config)
    serve_cfg = cell.config["serve"]
    engine = dict(serve_cfg["engine"])
    cluster.require_tpu_resource(cell.chips)
    deployment = serve.deployment(
        _replica_class(), name="BenchLLM",
        ray_actor_options={"resources": {"TPU": cell.chips},
                           "max_concurrency":
                               int(serve_cfg["max_concurrency"])})
    who = None
    try:
        t_run = time.monotonic()
        handle = serve.run(deployment.bind(sizes, seed, engine,
                                           cell.family_name, cell.root))
        who = _call(handle, "whoami", deadline=REPLICA_READY_DEADLINE_S)
        worker_ready_s = time.monotonic() - t_run
        cluster.check_lease_holder(who, cell.chips, platform)
        buckets = traffic.buckets_of(cell.traffic, engine["page_size"],
                                     engine["max_len"])
        warm = _call(handle, "warmup", buckets, WARMUP_NEW_TOKENS)
        requests = traffic.serve_requests(cell.traffic, seed,
                                          sizes["vocab_size"], seconds)
        _call(handle, "window_open")
        t0 = time.monotonic()
        setup_s = t0 - t_start
        ctx = Context(handle, requests, t0, seconds, cell.traffic)
        tracer = None
        trace_marks = None
        if trace:
            box = {}

            def trace_middle():
                a, b = _trace_slot(seconds)
                time.sleep(max(0.0, t0 + a - time.monotonic()))
                box["start"] = _call(handle, "trace_start")
                time.sleep(max(0.0, t0 + b - time.monotonic()))
                box["stop"] = _call(handle, "trace_stop")

            tracer = threading.Thread(target=trace_middle, daemon=True)
            tracer.start()
        threads = schedule(ctx)
        drain_until = t0 + seconds + DRAIN_S
        for t in threads:
            t.join(max(0.0, drain_until - time.monotonic()))
        drained_s = time.monotonic() - (t0 + seconds)
        if tracer is not None:
            tracer.join(60.0)
            trace_marks = box.get("stop")
        closed = _call(handle, "window_close")
        reduced = _call(handle, "trace_result") if trace else None
        spans = [dict(s) for s in ctx.spans]
        samples = _reference_samples(spans, ctx.requests, engine)
        diagnose = int(os.environ.get(DIAGNOSE_ENV) or 0)
        compared = _call(handle, "check_reference", samples,
                         LOGIT_TIE_TOLERANCE, diagnose) if samples else []
        if diagnose:
            from benchmarks.harness import diagnose as dg

            by_rid = {s["rid"]: s for s in spans}
            for c in compared:
                if c["over"]:
                    c["diagnosis"]["events"] = dg.events_near(
                        by_rid[c["rid"]], closed["spans"],
                        [o[0] for o in c["over"]])
    finally:
        serve.shutdown()
        if who is not None:
            released_s = cluster.wait_for_exit(who["pid"])

    log(phase="setup", worker_ready_s=worker_ready_s,
        init_params_s=who["init_params_s"], warmup=warm, buckets=buckets,
        setup_s=setup_s, param_bytes=who["param_bytes"],
        kv_pool_pages=who["kv_pool_pages"],
        compile_cache_dir=who["compile_cache_dir"])
    t1 = t0 + seconds
    done = [s for s in spans if s["error"] is None
            and s["tokens"] == s["want"] and s.get("done") is not None]
    failed = len(spans) - len(done)
    lateness = [s["sent"] - s["due"] for s in spans]
    log(phase="load", attempted=len(spans), failed=failed,
        drained_s=drained_s, released_s=released_s,
        generator_late_ms_p50=stats.median(lateness) * 1e3,
        generator_late_ms_max=max(lateness) * 1e3,
        errors=sorted({s["error"] for s in spans if s["error"]})[:3],
        compiles_in_window=closed["compiles_in_window"],
        memory=closed["memory"],
        reference=compared, trace_marks=trace_marks)

    problems = []
    if failed:
        problems.append(f"{failed} of {len(spans)} requests failed or did "
                        f"not finish within {DRAIN_S:.0f}s of the window")
    if closed["compiles_in_window"]:
        problems.append(f"{closed['compiles_in_window']} programs were "
                        "lowered or compiled inside the window")
    if closed["handoff_fallbacks"]:
        problems.append("prefill->decode KV handoff fell back off the "
                        "device plane")
    if not compared:
        problems.append("no request was compared with the reference")
    problems.extend(filter(None, map(judge, compared)))
    checked = [
        f"request {c['rid']} (prompt {c['prompt_len']}, {c['tokens']} "
        f"tokens): widest gap kept {c['kept_max_gap']:.4f}, limit "
        f"{LOGIT_TIE_TOLERANCE}; positions set aside {c['set_aside'] or 0}, "
        f"limit {int(SET_ASIDE_SHARE * c['tokens'])}" for c in compared]

    ttft = [stats.ttft_ms(s) for s in spans if s["first"] is not None]
    tpot = [v for v in (stats.tpot_ms(s) for s in done) if v is not None]
    # Open loop: the tokens of the requests due in the window, completed.
    # Closed loop: what the clients received inside the window (a slower
    # system is sent less, so the work is what arrived in the time).
    if "rate_rps" in cell.traffic:
        rate_name = "out_tokens_per_s"
        out_tokens = sum(s["tokens"] for s in done)
    else:
        rate_name = "batch_tokens_per_s"
        out_tokens = sum(stats.tokens_in_window(s, t0, t1) for s in spans)
    if not ttft or not tpot or not out_tokens:
        raise BenchmarkError(f"no request completed: {problems}")
    # What a serve run can report; `BENCHMARK.json` says which of these a
    # cell is judged by (`ttft_p90_ms` by none today: PERF.md, section 2).
    end_to_end = {
        rate_name: out_tokens / seconds,
        "ttft_p90_ms": stats.percentile(ttft, 90),
        "tpot_p90_ms": stats.percentile(tpot, 90),
        "setup_s": setup_s,
    }
    device = {"platform": who["platform"], "kind": who["kind"],
              "count": who["count"],
              "memory_peak_bytes": closed["memory_peak_bytes"]}
    obs = {"sizes": sizes, "config": cell.config, "traffic": cell.traffic,
           "family": cell.family_name,
           "device": device, "window": (t0, t1), "seconds": seconds,
           "client_spans": spans, "replica_spans": closed["spans"],
           "samples": closed["samples"], "max_batch": closed["max_batch"],
           "trace": reduced, "worker_ready_s": worker_ready_s,
           "end_to_end": end_to_end,
           "peaks": kernel_costs.peaks(who["kind"])
           if who["platform"] == "tpu" else None}
    return {"correct": not problems, "problems": problems,
            "checked": checked, "attempted": len(spans), "failed": failed,
            "end_to_end": end_to_end, "device": device, "obs": obs}


def judge(c: dict) -> str | None:
    """What is wrong with one compared sample, if anything.  Every position
    the reference did not set aside is held to LOGIT_TIE_TOLERANCE, and at
    most SET_ASIDE_SHARE of a sample's positions may be set aside."""
    where = f"prompt of {c['prompt_len']}, request {c['rid']}"
    if not c["kept_max_gap"] <= LOGIT_TIE_TOLERANCE:
        kept = [o[:2] for o in c["over"]
                if o[0] not in (c.get("set_aside_at") or ())]
        return (f"engine token {c['kept_max_gap']:.4f} under the "
                f"reference's best logit ({where}; [position, gap] "
                f"{kept[:8]}); tolerance {LOGIT_TIE_TOLERANCE}")
    if (c["set_aside"] or 0) > SET_ASIDE_SHARE * c["tokens"]:
        return (f"the reference set aside {c['set_aside']} of "
                f"{c['tokens']} positions ({where}); at most "
                f"{SET_ASIDE_SHARE:.0%} may be")
    return None


def _replica_class():
    from benchmarks.harness.replica import BenchLLM

    return BenchLLM


def _reference_samples(spans, requests, engine) -> list:
    """One finished request from each of up to four prompt buckets (the
    smallest to the largest the window saw), the first of each in rid
    order: the same choice in every run.  Where WIDE_REFERENCE_ENV holds a
    number, the first that many finished requests instead: how a
    `benchmark` issue measures the spread the tolerance has to cover."""
    by_rid = {r.rid: r for r in requests}
    wide = int(os.environ.get(WIDE_REFERENCE_ENV) or 0)
    if wide:
        done = [s for s in sorted(spans, key=lambda s: s["rid"])
                if s["error"] is None and s["tokens"] == s["want"]]
        return [_sample(by_rid, s) for s in done[:wide]]
    by_bucket: dict = {}
    for s in sorted(spans, key=lambda s: s["rid"]):
        if s["error"] is None and s["tokens"] == s["want"]:
            b = traffic.prompt_bucket(s["prompt_len"], engine["page_size"],
                                      engine["max_len"])
            by_bucket.setdefault(b, s)
    buckets = sorted(by_bucket)
    if len(buckets) > REFERENCE_SAMPLES:
        step = (len(buckets) - 1) / (REFERENCE_SAMPLES - 1)
        buckets = [buckets[round(i * step)] for i in range(REFERENCE_SAMPLES)]
    return [_sample(by_rid, by_bucket[b]) for b in buckets]


def _sample(by_rid: dict, span: dict) -> dict:
    return {"rid": span["rid"], "output": span["output"],
            "prompt": by_rid[span["rid"]].prompt_tokens}
