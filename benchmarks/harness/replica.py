"""The system under test for the serve cells: the framework's `LLMServer`
(paged `LLMEngine` inside), with weights made on the device from the seed
and the benchmark's own spans around the calls into it.  Nothing inside
`ray_tpu/` is changed or patched: the spans are taken at the boundary
(`engine.submit` -> tokens yielded), the occupancy from the engine's public
gauges, the trace by `jax.profiler` in this process, which holds the chip.
"""

from __future__ import annotations

import bisect
import threading
import time

from ray_tpu.serve.llm import LLMServer

from benchmarks.harness import cluster, loader, trace_reduce

SAMPLE_HZ = 20.0
JAX_SEED_MASK = 0x7FFFFFFF   # --seed may pass 2**31; PRNGKey takes 31 bits


def seeded_params(model, seed: int):
    """All weights in one jitted call, on the device, in the served type."""
    import jax
    import jax.numpy as jnp

    # The key is an ARGUMENT: a seed closed over would be a constant of the
    # program, and every new seed would miss the compile cache.
    params = jax.jit(lambda key: model.init(
        key, jnp.zeros((1, 8), jnp.int32)))(
            jax.random.PRNGKey(seed & JAX_SEED_MASK))
    return jax.block_until_ready(params)


class BenchLLM(LLMServer):
    def __init__(self, sizes: dict, seed: int, engine: dict,
                 family: str = loader.DEFAULT_FAMILY,
                 root: str = loader.REPO_ROOT):
        cluster.uncap_compile_cache()
        self._compiles = cluster.CompileCounter()
        t0 = time.monotonic()
        self._sizes = sizes
        self._family = loader.load_family(family, root)
        self._cfg = self._family.program_config(sizes)
        self._params = seeded_params(self._family.model(self._cfg), seed)
        self._init_params_s = time.monotonic() - t0
        super().__init__(self._cfg, self._params, **engine)
        self._lock = threading.Lock()
        self._spans: list = []
        self._live: dict = {}       # rid -> [prompt_len, tokens yielded]
        self._samples: list = []
        self._sampling = threading.Event()
        self._sampler: threading.Thread | None = None
        self._tracer: trace_reduce.Tracer | None = None
        self._compiles_at_open = 0

    # ---- the request path (what Serve calls) -----------------------------

    def __call__(self, payload: dict):
        from ray_tpu.models.generate import SamplingParams

        rid = payload["rid"]
        prompt = payload["prompt_tokens"]
        sp = SamplingParams(max_new_tokens=int(payload["max_new_tokens"]))
        span = {"rid": rid, "prompt_len": len(prompt),
                "submit": time.monotonic(), "first": None, "last": None,
                "tokens": 0}
        live = [len(prompt), 0]
        handle = self.engine.submit(prompt, sp)
        try:
            for tok in handle:
                now = time.monotonic()
                if span["first"] is None:
                    span["first"] = now
                    with self._lock:
                        self._live[rid] = live
                span["last"] = now
                span["tokens"] += 1
                live[1] += 1
                yield tok
        finally:
            with self._lock:
                self._live.pop(rid, None)
                self._spans.append(span)

    # ---- set-up -----------------------------------------------------------

    def whoami(self) -> dict:
        import jax

        leaves = jax.tree_util.tree_leaves(self._params)
        eng = self.engine
        return dict(cluster.device_report(),
                    param_bytes=sum(int(x.nbytes) for x in leaves),
                    init_params_s=self._init_params_s,
                    kv_pool_pages=int(eng._alloc.num_pages),
                    batch_prefill_width=int(eng._batch_prefill_width))

    def warmup(self, buckets: list, max_new: int) -> dict:
        """Compile every program the cell's traffic can reach: for each
        prompt bucket the batched prefill (requests admitted together) and
        the single one, each with its page writer and sampler, and the
        decode chunk.  The engine's loop is paused while a group is
        submitted, so that it is admitted as one group."""
        from ray_tpu.models.generate import SamplingParams

        t0 = time.monotonic()
        eng = self.engine
        for i, b in enumerate(buckets):
            n = min(b, eng.max_len - max_new)
            prompt = [1 + (i % 97) for i in range(n)]
            for group in (2, 1):
                # The decode chunk is one program: the first group runs it,
                # the others stop at their first token (no chunk to wait for).
                sp = SamplingParams(
                    max_new_tokens=max_new if (i, group) == (0, 2) else 1)
                eng.quiesce_for_drain()
                handles = [eng.submit(prompt, sp) for _ in range(group)]
                eng.resume()
                for h in handles:
                    h.tokens()
        return {"warmup_s": time.monotonic() - t0,
                "compiles": self._compiles.count}

    # ---- the measured window ----------------------------------------------

    def _sample_loop(self) -> None:
        eng = self.engine
        while self._sampling.is_set():
            with self._lock:
                resident = sum(p + n for p, n in self._live.values())
                decoding = len(self._live)
            self._samples.append(
                (time.monotonic(), eng.num_active(), eng.queue_depth(),
                 decoding, resident))
            time.sleep(1.0 / SAMPLE_HZ)

    def window_open(self) -> dict:
        with self._lock:
            self._spans = []
        self._samples = []
        self._compiles_at_open = self._compiles.count
        self._sampling.set()
        self._sampler = threading.Thread(target=self._sample_loop,
                                         daemon=True, name="bench-sampler")
        self._sampler.start()
        return {"t": time.monotonic()}

    def window_close(self) -> dict:
        from ray_tpu._private import device_objects

        self._sampling.clear()
        if self._sampler is not None:
            self._sampler.join(2.0)
        with self._lock:
            spans = list(self._spans)
        return {"spans": spans, "samples": list(self._samples),
                "compiles_in_window":
                    self._compiles.count - self._compiles_at_open,
                "max_batch": self.engine.max_batch,
                "handoff_fallbacks": int(self.engine.handoff_fallbacks),
                "plane_counters": device_objects.counters(),
                "memory_peak_bytes": cluster.memory_peak_bytes(),
                "memory": cluster.memory_headroom()}

    # ---- the profiler, in the process that holds the chip ------------------

    def trace_start(self) -> dict:
        self._tracer = trace_reduce.Tracer()
        self._tracer.start()
        return dict(self._tracer.marks)

    def trace_stop(self) -> dict:
        self._tracer.stop()
        return dict(self._tracer.marks)

    def trace_result(self) -> dict | None:
        """Reduce the trace here (reading it needs no backend, but this is
        the process that wrote it) and return the small result."""
        if self._tracer is None:
            return None
        with self._lock:
            spans = [s for s in self._spans if s["first"] is not None]
        return self._tracer.reduce(lambda off: _gap_labeller(spans, off))

    # ---- correctness, outside the window ----------------------------------

    def reference_logits(self, prompt: list, tokens: list, **how):
        """The plain reference's logits at the positions that produced
        `tokens`, teacher-forced over prompt + tokens on these weights."""
        import numpy as np

        seq = list(prompt) + list(tokens[:-1])
        rows = list(range(len(prompt) - 1, len(seq)))
        # One shape, one compile; the padding is causal and has no effect.
        padded = seq + [0] * (self.engine.max_len - len(seq))
        return np.asarray(self._family.reference.logits(
            self._params, self._sizes, padded, rows, **how))

    def check_reference(self, samples: list, tolerance: float,
                        diagnose: int = 0) -> list:
        """For each sample (`rid`, `prompt`, `output`): one teacher-forced
        pass of the plain reference over prompt + generated, on these
        weights; at every generated position k, how far the reference's
        logit of the engine's token lies under the reference's own best.
        Every gap over `tolerance` is returned with its k (`over`).

        Where a sample has such gaps the reference is run again with the
        roundings a bfloat16 server makes (`reference.ROUNDINGS`, one
        more a pass): a position at which the reference's OWN best token
        falls more than `tolerance` under the rounded pass's best is one
        the served type cannot decide, and is set aside.  The engine's
        tokens play no part in that.  `serve_common.judge` holds every
        position kept to `tolerance` and caps the share set aside.
        `diagnose` (BENCH_DIAGNOSE): make the rounded passes on that many
        leading samples whatever they show, and the discriminating runs
        of `harness/diagnose.py` on every sample with a gap."""
        import numpy as np

        out = []
        for i, sample in enumerate(samples):
            prompt, got = sample["prompt"], sample["output"]
            at = np.arange(len(got))
            lg = self.reference_logits(prompt, got)
            top2 = np.partition(lg, -2, axis=-1)[:, -2:]
            best, margin = top2[:, 1], top2[:, 1] - top2[:, 0]
            gap = best - lg[at, np.asarray(got)]
            over = np.flatnonzero(gap > tolerance)
            one = {"rid": sample.get("rid"), "prompt_len": len(prompt),
                   "tokens": len(got), "agree": int((gap == 0).sum()),
                   "max_logit_gap": float(gap.max()),
                   "gaps": sorted(float(g) for g in gap if g > 0)[-8:],
                   "over": [[int(k), float(gap[k]), float(best[k]),
                             float(margin[k])] for k in over],
                   "mean_top_logit": float(best.mean()),
                   "median_top2_margin": float(np.median(margin)),
                   "set_aside": None, "kept_max_gap": float(gap.max())}
            if len(over) or i < diagnose:
                own = lg.argmax(-1)
                passes = [self.reference_logits(prompt, got, rounded=level)
                          for level in
                          range(1, len(self._family.reference.ROUNDINGS))]
                moved = np.stack([p.max(-1) - p[at, own] for p in passes])
                aside = (moved > tolerance).any(0)
                one.update(
                    set_aside=int(aside.sum()),
                    set_aside_at=[int(k) for k in np.flatnonzero(aside)],
                    set_aside_by_pass=[int((m > tolerance).sum())
                                       for m in moved],
                    kept_max_gap=float(gap[~aside].max(initial=0.0)))
                if diagnose:
                    from benchmarks.harness import diagnose as dg

                    one["diagnosis"] = dg.sample(
                        self, sample, lg, over, moved, passes, tolerance)
            out.append(one)
        return out


def _gap_labeller(spans: list, off_ns: float):
    """Name an idle gap by what the benchmark's spans can tell today."""
    busy = trace_reduce.union_intervals(
        [["", s["submit"] * 1e9 - off_ns,
          (s["last"] - s["submit"]) * 1e9] for s in spans])
    starts = [iv[0] for iv in busy]
    waiting = trace_reduce.union_intervals(
        [["", s["submit"] * 1e9 - off_ns,
          (s["first"] - s["submit"]) * 1e9] for s in spans])
    wstarts = [iv[0] for iv in waiting]

    def inside(intervals, begins, t):
        i = bisect.bisect_right(begins, t) - 1
        return i >= 0 and t < intervals[i][1]

    def label(t0, t1):
        mid = (t0 + t1) / 2
        if not inside(busy, starts, mid):
            return "no request in flight"
        if inside(waiting, wstarts, mid):
            return "engine loop, a request awaiting its first token " \
                   "(unattributed)"
        return "engine loop between dispatches (unattributed)"

    return label
