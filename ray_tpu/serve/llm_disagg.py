"""Disaggregated LLM serving: prefill and decode as separate replica pools.

The single-process LLMEngine couples prefill compute to decode batching:
one replica runs both phases, so they fight for the same device and
scale on the same signal. This module splits them (reference: the
vLLM-style disaggregated prefill/decode deployments Serve LLM apps
wrap):

- **Prefill pool** (`PrefillServer`): bucketed whole-prompt prefill plus
  a cross-request prefix cache keyed on the prompt tokens — a full hit
  skips prefill compute entirely, a partial hit prefills only the
  suffix. Each replica returns the per-request KV as a device object
  (the router calls it with `tensor_transport="device"`), so the KV is
  pinned where it was produced and never travels through the router.
- **Decode pool** (`DecodeServer`): hosts a continuous-batching
  LLMEngine; `decode_stream` resolves the prefill KV over the cheapest
  device-plane route (same-mesh collective, counted host fallback) into
  a free slot via `submit_prefilled` — the happy path moves KV
  producer→consumer directly.
- **Router** (`DisaggHandle`): picks a prefill replica, passes the
  device ObjectRef (nested, unresolved) to a decode replica, and
  streams tokens back. A decode replica lost mid-stream resumes with
  ZERO dropped or duplicated tokens: a drained node evacuates the
  stream's KV + cursor through `device_objects.evacuate()` to the
  router, which replays undelivered tokens and re-submits the stream on
  a surviving replica; a hard crash falls back to a deterministic
  re-prefill of prompt + delivered tokens.
- **Per-pool autoscaling**: each pool carries an AutoscalingConfig with
  a replica-reported named metric — queue depth / TTFT for prefill,
  tokens-in-flight for decode — polled by the ServeController instead
  of the single handle-side queue-depth signal.
"""

from __future__ import annotations

import queue
import threading
import time
import uuid
from collections import OrderedDict, deque

import numpy as np

from ray_tpu.models.generate import SamplingParams
from ray_tpu.models.llama import LlamaConfig, LlamaModel, init_kv_caches
from ray_tpu.serve.llm import LLMEngine, _Prefilled


def _note(event: str, n: int = 1) -> None:
    """Tick the serve-disagg gauges; never allowed to break the path."""
    try:
        from ray_tpu.util.metrics import note_serve_disagg

        note_serve_disagg(event, n)
    except Exception:
        pass


# ---------------------------------------------------------------------------
# Prefix cache
# ---------------------------------------------------------------------------


class PrefixCache:
    """Cross-request KV cache keyed on prompt tokens (LRU, bounded).

    Entries hold the host-side per-layer KV for one full prompt plus the
    last-position logits. Lookup semantics:

      full    — the exact prompt was seen before: reuse its KV AND its
                last-token logits (zero prefill compute; only sampling
                runs, with THIS request's params).
      partial — a cached prompt is a strict prefix of the new one:
                prefill only the suffix on top of the cached KV.
      miss    — run the whole bucketed prefill.
    """

    def __init__(self, max_entries: int = 32):
        self.max_entries = max(1, max_entries)
        self._entries: OrderedDict[tuple, dict] = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def lookup(self, prompt) -> tuple[str, dict | None]:
        key = tuple(int(t) for t in prompt)
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                return "full", entry
            best_key, best = None, None
            for k, e in self._entries.items():
                n = len(k)
                if n < len(key) and key[:n] == k:
                    if best_key is None or n > len(best_key):
                        best_key, best = k, e
            if best is not None:
                self._entries.move_to_end(best_key)
                self.hits += 1
                return "partial", best
            self.misses += 1
            return "miss", None

    def insert(self, prompt, kv_host: list, last_logits) -> None:
        key = tuple(int(t) for t in prompt)
        with self._lock:
            self._entries[key] = {
                "prefix_len": len(key),
                "kv": kv_host,  # [(k, v)] per layer, numpy (Hkv, plen, D)
                "logits": np.asarray(last_logits),
            }
            self._entries.move_to_end(key)
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
                self.evictions += 1

    def stats(self) -> dict:
        with self._lock:
            n = len(self._entries)
        total = self.hits + self.misses
        return {"entries": n, "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions,
                "hit_rate": self.hits / total if total else 0.0}


# ---------------------------------------------------------------------------
# Prefill pool
# ---------------------------------------------------------------------------


class PrefillEngine:
    """Compiled prefill programs for the prefill pool: bucketed
    whole-prompt prefill plus a suffix variant that continues on top of
    a cached KV prefix (the prefix-cache partial-hit path)."""

    def __init__(self, cfg: LlamaConfig, params, *, max_len: int = 1024,
                 rng_seed: int = 0):
        import jax
        import jax.numpy as jnp

        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.model = LlamaModel(cfg)
        self._jax, self._jnp = jax, jnp
        self._rng = jax.random.PRNGKey(rng_seed)
        model, cfg_, max_len_ = self.model, cfg, max_len

        @jax.jit
        def prefill_one(params, tokens):
            positions = jnp.arange(tokens.shape[1])[None, :]
            caches1 = init_kv_caches(cfg_, 1, max_len_)
            logits, new = model.apply(params, tokens, positions,
                                      kv_caches=caches1)
            return logits[0], [(k[0], v[0]) for k, v, _l in new]

        @jax.jit
        def prefill_suffix(params, tokens, start, kv_prefix):
            # tokens: (1, sbucket) right-padded suffix at absolute
            # positions start.. ; kv_prefix per layer (Hkv, max_len, D)
            # valid on [0, start). The write window [start, start+sb)
            # must fit max_len (callers guard) or dynamic_update_slice
            # clamping would relocate it over the prefix.
            positions = start + jnp.arange(tokens.shape[1])[None, :]
            caches1 = [(k[None], v[None], start) for k, v in kv_prefix]
            logits, new = model.apply(params, tokens, positions,
                                      kv_caches=caches1)
            return logits[0], [(k[0], v[0]) for k, v, _l in new]

        self._prefill_one = prefill_one
        self._prefill_suffix = prefill_suffix

    def _bucket(self, n: int) -> int:
        b = 16
        while b < n:
            b *= 2
        return min(b, self.max_len)

    def _sample_first(self, last_logits, sp: SamplingParams) -> int:
        from ray_tpu.models.generate import sample_logits

        self._rng, srng = self._jax.random.split(self._rng)
        tok = sample_logits(self._jnp.asarray(last_logits)[None], srng, sp)
        return int(np.asarray(tok)[0])

    def prefill(self, prompt: np.ndarray, sp: SamplingParams,
                cache: PrefixCache | None = None) -> dict:
        """Run (or skip, on a cache hit) prefill for one prompt. Returns
        {"kv": [(k, v)] jax arrays trimmed to prompt_len, "first_token",
        "prompt_len", "kv_len", "prefix_hit"}."""
        jnp = self._jnp
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        plen = len(prompt)
        hit, entry = cache.lookup(prompt) if cache is not None \
            else ("miss", None)
        if hit == "partial":
            start = entry["prefix_len"]
            sbucket = self._bucket(plen - start)
            if start + sbucket > self.max_len:
                # Suffix write window would clamp past max_len: run the
                # whole-prompt path instead (correctness over reuse).
                hit, entry = "miss", None
        if hit == "full":
            kv = [(jnp.asarray(k), jnp.asarray(v)) for k, v in entry["kv"]]
            first = self._sample_first(entry["logits"], sp)
            _note("prefix_full_hits")
            return {"kv": kv, "first_token": first, "prompt_len": plen,
                    "kv_len": plen, "prefix_hit": "full"}
        if hit == "partial":
            start = entry["prefix_len"]
            sbucket = self._bucket(plen - start)
            suffix = np.zeros((1, sbucket), np.int32)
            suffix[0, : plen - start] = prompt[start:]
            kv_prefix = []
            for k, v in entry["kv"]:
                Hkv, _pl, D = k.shape
                kp = np.zeros((Hkv, self.max_len, D), k.dtype)
                vp = np.zeros((Hkv, self.max_len, D), v.dtype)
                kp[:, :start] = k[:, :start]
                vp[:, :start] = v[:, :start]
                kv_prefix.append((jnp.asarray(kp, self.cfg.dtype),
                                  jnp.asarray(vp, self.cfg.dtype)))
            logits, kv_full = self._prefill_suffix(
                self.params, jnp.asarray(suffix), jnp.int32(start),
                kv_prefix)
            last_logits = logits[plen - start - 1]
            _note("prefix_partial_hits")
        else:
            bucket = self._bucket(plen)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :plen] = prompt
            logits, kv_full = self._prefill_one(self.params,
                                                jnp.asarray(padded))
            last_logits = logits[plen - 1]
        kv = [(k[:, :plen], v[:, :plen]) for k, v in kv_full]
        if cache is not None:
            cache.insert(prompt,
                         [(np.asarray(k), np.asarray(v)) for k, v in kv],
                         np.asarray(last_logits))
        first = self._sample_first(last_logits, sp)
        return {"kv": kv, "first_token": first, "prompt_len": plen,
                "kv_len": plen, "prefix_hit": hit}


class PrefillServer:
    """Prefill-pool deployment callable.

    Requests funnel through an internal queue serviced by ONE worker
    thread (the compiled programs are single-device; serialization also
    makes queue_depth an honest autoscaling signal even though the
    replica actor runs with max_concurrency lanes). The router calls
    `prefill` with tensor_transport="device", so the returned KV arrays
    pin HERE and ship over the device plane straight to decode."""

    def __init__(self, cfg: LlamaConfig, params, *, max_len: int = 1024,
                 prefix_cache_size: int = 32, rng_seed: int = 0):
        self.engine = PrefillEngine(cfg, params, max_len=max_len,
                                    rng_seed=rng_seed)
        self.cache = PrefixCache(prefix_cache_size)
        self._q: queue.Queue = queue.Queue()
        self._ttft = deque(maxlen=256)
        self._served = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="prefill-engine")
        self._thread.start()

    def _loop(self):
        while not self._stop.is_set():
            try:
                item = self._q.get(timeout=0.1)
            except queue.Empty:
                continue
            payload, done, holder, t0 = item
            try:
                sp = _sampling_from(payload)
                holder["result"] = self.engine.prefill(
                    payload["prompt_tokens"], sp, self.cache)
            except BaseException as e:  # noqa: BLE001
                holder["error"] = e
            self._ttft.append(time.monotonic() - t0)
            self._served += 1
            done.set()

    def prefill(self, payload: dict) -> dict:
        done = threading.Event()
        holder: dict = {}
        self._q.put((payload, done, holder, time.monotonic()))
        if not done.wait(timeout=300):
            raise TimeoutError("prefill queue wait exceeded 300s")
        if "error" in holder:
            raise holder["error"]
        return holder["result"]

    def report_metrics(self) -> dict:
        ttft = sorted(self._ttft)
        pick = lambda q: ttft[min(len(ttft) - 1,  # noqa: E731
                                  int(q * len(ttft)))] if ttft else 0.0
        out = {
            "queue_depth": float(self._q.qsize()),
            "served": float(self._served),
            "ttft_p50_ms": pick(0.5) * 1e3,
            "ttft_p99_ms": pick(0.99) * 1e3,
        }
        for k, v in self.cache.stats().items():
            out[f"prefix_cache_{k}"] = float(v)
        return out

    def prepare_drain(self):
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and self._q.qsize():
            time.sleep(0.05)


def _sampling_from(payload: dict) -> SamplingParams:
    return SamplingParams(
        max_new_tokens=int(payload.get("max_new_tokens", 64)),
        temperature=float(payload.get("temperature", 0.0)),
        top_k=int(payload.get("top_k", 0)),
        top_p=float(payload.get("top_p", 1.0)),
        eos_token=payload.get("eos_token"))


# ---------------------------------------------------------------------------
# Decode pool
# ---------------------------------------------------------------------------


class DecodeServer:
    """Decode-pool deployment callable hosting one continuous-batching
    LLMEngine. `decode_stream` resolves the prefill pool's device-object
    KV in THIS process (cheapest route) and admits it via
    submit_prefilled — the KV never round-trips through the router.

    Zero-loss drain: a DrainNotice (node preemption) quiesces the
    engine, snapshots every in-flight stream (KV + cursor + token
    history), and pins the snapshots with the ROUTER as ref owner, so
    the raylet's drain pipeline evacuates them through
    device_objects.evacuate() to the router process for resume."""

    def __init__(self, cfg: LlamaConfig, params, *, max_batch: int = 4,
                 max_len: int = 1024, decode_chunk: int = 8,
                 page_size: int = 64, kv_pool_tokens: int = 0,
                 stream_buffer: int = 256):
        self.cfg = cfg
        self.engine = LLMEngine(cfg, params, max_batch=max_batch,
                                max_len=max_len, decode_chunk=decode_chunk,
                                page_size=page_size,
                                kv_pool_tokens=kv_pool_tokens,
                                stream_buffer=stream_buffer)
        self._router_wires: dict[str, object] = {}
        self._evac_streams = 0
        self._decode_requests = 0
        try:
            from ray_tpu._private import device_objects

            # Runs INSIDE device_objects.evacuate() before it gathers
            # pins — a DrainNotice listener would lose the race against
            # the raylet's evacuation step, which fires milliseconds
            # after the notice.
            device_objects.add_evacuation_preparer(self._evacuate_streams)
        except Exception:
            pass  # no runtime (unit tests drive the engine directly)

    def _evacuate_streams(self):
        try:
            if not self.engine.quiesce_for_drain(timeout=8.0):
                return
            snaps = self.engine.snapshot_active_streams()
            if not snaps:
                return
            from ray_tpu._private import device_objects
            from ray_tpu._private.api_internal import get_core_worker

            cw = get_core_worker()
            reg = device_objects.registry()
            for tag, snap in snaps.items():
                wire = self._router_wires.get(tag)
                if wire is None:
                    continue
                prefix = f"disagg:{tag}"
                i = 0
                for k, v in snap["kv"]:
                    reg.pin(f"{prefix}#{i}", k, cw)
                    reg.pin(f"{prefix}#{i + 1}", v, cw)
                    i += 2
                state = np.asarray([snap["lens"], snap["token"],
                                    snap["generated"], snap["prompt_len"]],
                                   np.float64)
                reg.pin(f"{prefix}#{i}", state, cw)
                # History LAST: the router polls this key as the
                # all-leaves-landed sentinel after repin.
                hist = np.asarray(snap["history"], np.int64)
                reg.pin(f"{prefix}#{i + 1}", hist, cw)
                reg.note_ref_owner(prefix, wire)
                self._evac_streams += 1
                _note("streams_evacuated")
        except Exception:
            pass  # the router's re-prefill fallback still covers us

    def decode_stream(self, meta: dict, kv_ref):
        import ray_tpu

        kv_obj = ray_tpu.get(kv_ref)  # device stubs resolve HERE
        sp = _sampling_from(meta)
        resume = meta.get("resume")
        if resume:
            pack = _Prefilled(kv_obj["kv"], resume["token"],
                              kv_obj["prompt_len"], resume["lens"],
                              resume["generated"], resume["history"],
                              emit_first=False)
        else:
            pack = _Prefilled(kv_obj["kv"], kv_obj["first_token"],
                              kv_obj["prompt_len"], 0, 0, [],
                              emit_first=True)
            pack.lens = int(kv_obj["kv_len"])
        tag = meta.get("rsid", "")
        if meta.get("router_wire") is not None:
            self._router_wires[tag] = meta["router_wire"]
        handle = self.engine.submit_prefilled(pack, sp, tag=tag)
        self._decode_requests += 1
        try:
            for tok in handle:
                yield tok
        finally:
            self._router_wires.pop(tag, None)

    def report_metrics(self) -> dict:
        from ray_tpu._private import device_objects

        out = self.engine.report_metrics()
        out["decode_requests"] = float(self._decode_requests)
        out["streams_evacuated"] = float(self._evac_streams)
        out["plane_counters"] = device_objects.counters()
        try:
            import ray_tpu

            out["node_id"] = ray_tpu.get_runtime_context().node_id
        except Exception:
            pass
        return out

    def prepare_drain(self):
        """Controller scale-in: wait for in-flight streams to finish
        (they keep draining over the replica's other concurrency lanes
        while this call blocks)."""
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if self.engine.num_active() == 0 and \
                    self.engine.queue_depth() == 0:
                return
            time.sleep(0.1)


# ---------------------------------------------------------------------------
# Router
# ---------------------------------------------------------------------------


class DisaggHandle:
    """Routes one request across the two pools: prefill (device-return
    KV) → decode (streamed tokens), with zero-loss resume when a decode
    replica dies mid-stream."""

    def __init__(self, prefill_handle, decode_handle, *, n_layers: int,
                 prefill_name: str = "", decode_name: str = "",
                 evac_wait_s: float = 6.0, max_resumes: int = 3):
        self._prefill = prefill_handle
        self._decode = decode_handle
        self._n_layers = n_layers
        self.prefill_name = prefill_name
        self.decode_name = decode_name
        self._evac_wait_s = evac_wait_s
        self._max_resumes = max_resumes
        self.stats = {"requests": 0, "completed": 0, "resumes": 0,
                      "replayed_tokens": 0, "evac_resumes": 0,
                      "fallback_reprefills": 0}
        try:
            from ray_tpu._private.api_internal import get_core_worker

            self._wire = get_core_worker().address.to_wire()
        except Exception:
            self._wire = None

    # -- pool plumbing --

    def _prefill_ref(self, payload: dict):
        """Run prefill on the least-loaded prefill replica with a
        device-object return: the KV pins on the prefill worker with
        THIS process as ref owner; only the descriptor travels."""
        idx, replica = self._prefill._pick_replica()
        try:
            return replica.handle_request.options(
                tensor_transport="device").remote(
                    "prefill", [payload], {}, "")
        finally:
            # The prefill pool scales on its replica-reported queue
            # depth, not handle-side outstanding counts.
            self._prefill._done(idx)

    def _decode_gen(self, meta: dict, kv_ref, attempts: int = 1):
        """Submit one decode stream. attempts > 1 rides out the window
        after a replica death where _pick_replica can still hand back
        the dead replica (the controller needs a health tick or two to
        recreate it and push the new set)."""
        last = None
        for _ in range(max(1, attempts)):
            try:
                return self._decode.options(
                    stream=True,
                    method_name="decode_stream").remote(meta, kv_ref)
            except Exception as e:  # dead replica / empty set mid-recreate
                last = e
                time.sleep(0.5)
        raise last

    def _read_evacuated(self, rsid: str) -> dict | None:
        """Poll this process's registry for a drain-evacuated stream
        snapshot (device_objects.handle_repin lands the pins here under
        their original keys). Returns None when no evacuation arrived
        within the window — the caller falls back to re-prefill."""
        from ray_tpu._private import device_objects

        reg = device_objects.registry()
        prefix = f"disagg:{rsid}"
        last_key = f"{prefix}#{2 * self._n_layers + 1}"
        deadline = time.monotonic() + self._evac_wait_s
        while reg.get(last_key) is None:
            if time.monotonic() >= deadline:
                return None
            time.sleep(0.2)
        kv = []
        for li in range(self._n_layers):
            k = reg.get(f"{prefix}#{2 * li}")
            v = reg.get(f"{prefix}#{2 * li + 1}")
            if k is None or v is None:
                return None
            kv.append((np.asarray(k), np.asarray(v)))
        state = np.asarray(reg.get(f"{prefix}#{2 * self._n_layers}"))
        hist = [int(t) for t in np.asarray(reg.get(last_key))]
        reg.release_prefix(prefix, counted=False)
        return {"kv": kv, "lens": int(state[0]), "token": int(state[1]),
                "generated": int(state[2]), "prompt_len": int(state[3]),
                "history": hist}

    def _reship_kv(self, snap: dict):
        """Pin the evacuated KV in THIS process and hand the new decode
        replica a device ref to it — the resume handoff rides the same
        plane as the original one."""
        import jax.numpy as jnp

        from ray_tpu._private import device_objects

        kv = [(jnp.asarray(k), jnp.asarray(v)) for k, v in snap["kv"]]
        return device_objects.device_put({
            "kv": kv, "prompt_len": snap["prompt_len"],
            "kv_len": snap["lens"], "first_token": snap["token"]})

    # -- request path --

    def stream(self, payload: dict):
        """Generator of tokens for one request across both pools."""
        rsid = uuid.uuid4().hex
        self.stats["requests"] += 1
        _note("streams_started")
        meta = {"rsid": rsid, "router_wire": self._wire,
                **{k: payload[k] for k in ("max_new_tokens", "temperature",
                                           "top_k", "top_p", "eos_token")
                   if k in payload}}
        max_new = int(payload.get("max_new_tokens", 64))
        eos = payload.get("eos_token")
        gen = self._decode_gen(meta, self._prefill_ref(payload))
        delivered: list[int] = []
        # A fallback re-prefill starts a fresh engine lineage whose
        # history/generated counters are LOCAL to it: `base` maps that
        # lineage's token 0 onto the global stream position.
        base = 0
        resumes = 0
        while True:
            try:
                for tok in gen:
                    delivered.append(tok)
                    yield tok
                self.stats["completed"] += 1
                _note("streams_completed")
                return
            except Exception:
                if resumes >= self._max_resumes:
                    raise
                resumes += 1
                self.stats["resumes"] += 1
                _note("stream_resumes")
                try:
                    gen.cancel()
                except Exception:
                    pass
                if len(delivered) >= max_new or \
                        (eos is not None and delivered
                         and delivered[-1] == eos):
                    # The replica died between the final token and the
                    # done signal — nothing left to resume.
                    self.stats["completed"] += 1
                    _note("streams_completed")
                    return
                snap = self._read_evacuated(rsid)
                if snap is not None:
                    self.stats["evac_resumes"] += 1
                    # Replay tokens the consumer never saw (the engine's
                    # history includes ones that were still queued or in
                    # a lost next_chunks reply).
                    for tok in snap["history"][len(delivered) - base:]:
                        delivered.append(tok)
                        self.stats["replayed_tokens"] += 1
                        yield tok
                    if len(delivered) >= max_new or \
                            (eos is not None and delivered
                             and delivered[-1] == eos):
                        self.stats["completed"] += 1
                        _note("streams_completed")
                        return
                    meta = dict(meta, resume={
                        "token": snap["token"], "lens": snap["lens"],
                        "generated": snap["generated"],
                        "history": snap["history"]})
                    gen = self._decode_gen(meta, self._reship_kv(snap),
                                           attempts=24)
                else:
                    # No evacuation landed (hard crash): deterministic
                    # re-prefill of prompt + delivered tokens. BOTH the
                    # prefill payload and the decode meta get the shrunk
                    # budget — the new engine stream starts at
                    # generated=0, so its max_new must exclude what was
                    # already streamed or it decodes past the request's
                    # budget.
                    self.stats["fallback_reprefills"] += 1
                    _note("fallback_reprefills")
                    base = len(delivered)
                    payload2 = dict(payload)
                    payload2["prompt_tokens"] = list(
                        np.asarray(payload["prompt_tokens"],
                                   np.int64).reshape(-1)) + delivered
                    payload2["max_new_tokens"] = max_new - base
                    meta = dict(meta, max_new_tokens=max_new - base)
                    meta.pop("resume", None)
                    gen = self._decode_gen(meta, self._prefill_ref(payload2),
                                           attempts=24)

    def generate(self, payload: dict) -> list[int]:
        return list(self.stream(payload))

    def pool_metrics(self) -> dict:
        """Replica-reported metrics for both pools (one poll fan-out)."""
        import ray_tpu

        out: dict = {}
        for label, handle in (("prefill", self._prefill),
                              ("decode", self._decode)):
            rows = []
            for r in handle._get_replicas():
                try:
                    rows.append(ray_tpu.get(r.report_metrics.remote(),
                                            timeout=10))
                except Exception:
                    pass
            out[label] = rows
        return out


# ---------------------------------------------------------------------------
# Deployment helper
# ---------------------------------------------------------------------------


def deploy_disagg(cfg: LlamaConfig, params, *, name: str = "llm",
                  prefill_replicas: int = 2, decode_replicas: int = 2,
                  max_batch: int = 4, max_len: int = 512,
                  decode_chunk: int = 4, page_size: int = 64,
                  kv_pool_tokens: int = 0, prefix_cache_size: int = 32,
                  stream_buffer: int = 256,
                  prefill_autoscaling: dict | None = None,
                  decode_autoscaling: dict | None = None,
                  prefill_actor_options: dict | None = None,
                  decode_actor_options: dict | None = None) -> DisaggHandle:
    """Deploy the two pools under one router and return a DisaggHandle.

    Pool autoscaling configs default to the per-pool named metrics:
    prefill scales on queue_depth, decode on tokens_in_flight. Replicas
    run with max_concurrency > 1 — required so prepare_drain (blocking
    until streams finish) cannot deadlock the next_chunks pulls those
    streams need."""
    from ray_tpu import serve

    prefill_asc = prefill_autoscaling
    if prefill_asc is None:
        prefill_asc = {"min_replicas": prefill_replicas,
                       "max_replicas": prefill_replicas}
    prefill_asc.setdefault("metric", "queue_depth")
    prefill_asc.setdefault("target_value", 4.0)
    decode_asc = decode_autoscaling
    if decode_asc is None:
        decode_asc = {"min_replicas": decode_replicas,
                      "max_replicas": decode_replicas}
    decode_asc.setdefault("metric", "tokens_in_flight")
    decode_asc.setdefault("target_value", float(max_batch * 64))

    prefill_dep = serve.deployment(
        PrefillServer, name=f"{name}-prefill",
        num_replicas=prefill_replicas,
        ray_actor_options={"max_concurrency": 8,
                           **(prefill_actor_options or {})},
        autoscaling_config=prefill_asc,
    ).bind(cfg, params, max_len=max_len,
           prefix_cache_size=prefix_cache_size)
    decode_dep = serve.deployment(
        DecodeServer, name=f"{name}-decode",
        num_replicas=decode_replicas,
        ray_actor_options={"max_concurrency": 16,
                           **(decode_actor_options or {})},
        autoscaling_config=decode_asc,
    ).bind(cfg, params, max_batch=max_batch, max_len=max_len,
           decode_chunk=decode_chunk, page_size=page_size,
           kv_pool_tokens=kv_pool_tokens, stream_buffer=stream_buffer)
    prefill_handle = serve.run(prefill_dep)
    decode_handle = serve.run(decode_dep)
    return DisaggHandle(prefill_handle, decode_handle,
                        n_layers=cfg.n_layers,
                        prefill_name=f"{name}-prefill",
                        decode_name=f"{name}-decode")
