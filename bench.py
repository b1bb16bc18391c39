"""Benchmarks.

`python bench.py` — flagship decoder training throughput + MFU on the
attached TPU; prints ONE JSON line: {"metric", "value", "unit",
"vs_baseline"}.  Baseline: BASELINE.md north star — ≥45% MFU for
Llama-family training (vs_baseline = achieved_MFU / 0.45).

`--device-handoff` and `--serve-disagg` are device measurements too.
All three refuse to run without a TPU: a number from another backend is
not a device number.  `--actor-churn`, `--control-soak` and
`--scale-chaos` measure the control plane and need no accelerator.
"""

from __future__ import annotations

import json
import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.abspath(__file__))

_DEVICE_HANDOFF_MODE = "--device-handoff" in sys.argv[1:]
_SERVE_DISAGG_MODE = "--serve-disagg" in sys.argv[1:]
_ACTOR_CHURN_MODE = "--actor-churn" in sys.argv[1:]
_CONTROL_SOAK_MODE = "--control-soak" in sys.argv[1:]
_SCALE_CHAOS_MODE = "--scale-chaos" in sys.argv[1:]

# Peak bf16 FLOP/s of one chip, keyed by jax's `device_kind` (Google
# Cloud documentation, "TPU v5e" / "TPU v4" system architecture pages).
# A device that is not here is an error, not a default.
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,
    "TPU v4": 275e12,
}


def _require_tpu(mode: str):
    """The jax module, on a TPU backend, or exit with a message."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"bench: {mode} measures a TPU and the JAX backend here "
                 f"is {backend!r}; run it on the chip (no CPU stand-in)")
    return jax


def main():
    jax = _require_tpu("the training benchmark")
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models.llama import (
        LlamaConfig, LlamaModel, count_flops_per_token, cross_entropy_loss)
    from ray_tpu.parallel import MeshConfig, TRANSFORMER_RULES, make_mesh
    from ray_tpu.train.spmd import (
        init_sharded_state, make_train_step, shard_train_step)
    from jax.sharding import NamedSharding, PartitionSpec as P

    kind = jax.devices()[0].device_kind
    if kind not in PEAK_FLOPS:
        sys.exit(f"bench: no peak FLOP/s on record for device_kind "
                 f"{kind!r}; add it to PEAK_FLOPS with its source")
    peak = PEAK_FLOPS[kind]
    bench_cfg = os.environ.get("RAY_TPU_BENCH_CONFIG", "1.2b")
    if bench_cfg == "max":
        # Max-fit config at the single-chip HBM edge (~2.7B params):
        # derisks the 7B north-star's memory behavior — bf16 params
        # (5.4 GiB) + bf16 grads + factored optimizer state (adafactor,
        # the standard choice at the memory edge) + full activation
        # remat ≈ 13-14 GiB of the v5e's 16. MFU drops vs the 1.2B
        # sweet spot (remat recomputes the forward).
        cfg = LlamaConfig(vocab_size=32000, d_model=2560, n_layers=24,
                          n_heads=20, n_kv_heads=20, d_ff=10240,
                          max_seq_len=2048, dtype=jnp.bfloat16,
                          attention="flash", remat=True)
        batch, seq, steps = 1, 2048, 8
    else:
        # ~1.2B-param decoder with Llama-7B head_dim (128): small per-step
        # batch keeps activations in HBM without remat; head_dim 64 would
        # waste half the MXU (see flash kernel block tuning in
        # ops/attention.py).
        cfg = LlamaConfig(vocab_size=32000, d_model=2048, n_layers=16,
                          n_heads=16, n_kv_heads=16, d_ff=8192,
                          max_seq_len=2048, dtype=jnp.bfloat16,
                          attention="flash", remat=False)
        batch, seq, steps = 2, 2048, 20

    model = LlamaModel(cfg)
    mesh = make_mesh(MeshConfig(dp=len(jax.devices())))
    tokens = jnp.zeros((batch, seq), jnp.int32)
    if bench_cfg == "max":
        # Factored second moments: full adam state (8 bytes/param fp32)
        # cannot fit beside a ~2.7B bf16 model on one 16 GiB chip.
        optimizer = optax.adafactor(3e-4)
    else:
        optimizer = optax.adamw(3e-4, weight_decay=0.01)
    state, specs = init_sharded_state(
        mesh, lambda t: model.init(jax.random.PRNGKey(0), t),
        TRANSFORMER_RULES, optimizer, tokens)

    def loss_fn(params, batch_):
        inp, tgt = batch_
        return cross_entropy_loss(model.apply(params, inp), tgt)

    step = make_train_step(loss_fn, optimizer)
    batch_spec = (P(("dp", "fsdp"), None), P(("dp", "fsdp"), None))
    sharded_step = shard_train_step(step, mesh, specs, batch_spec)

    rng = np.random.default_rng(0)
    data = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq + 1)),
                       jnp.int32)
    example = jax.device_put(
        (data[:, :-1], data[:, 1:]),
        jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), batch_spec,
                               is_leaf=lambda x: isinstance(x, P)))

    # Warmup/compile.
    state, metrics = sharded_step(state, example)
    first_loss = float(jax.block_until_ready(metrics["loss"]))
    assert np.isfinite(first_loss), f"non-finite loss {first_loss}"

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = sharded_step(state, example)
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0
    final_loss = float(metrics["loss"])
    assert np.isfinite(final_loss), f"non-finite loss {final_loss}"

    tokens_per_step = batch * seq
    tokens_per_sec = tokens_per_step * steps / dt
    flops_per_token = count_flops_per_token(cfg)
    mfu = tokens_per_sec * flops_per_token / (peak * len(jax.devices()))

    print(json.dumps({
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec / len(jax.devices()), 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {
            "mfu": round(mfu, 4),
            "device": {"platform": jax.devices()[0].platform,
                       "kind": kind, "count": len(jax.devices())},
            "config": bench_cfg,
            "params_millions": round(sum(
                int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(state.params)) / 1e6, 1),
            "batch": batch, "seq": seq, "steps": steps,
            "step_time_ms": round(dt / steps * 1000, 1),
        },
    }))


def device_handoff_main():
    """Device-handoff microbenchmark: device object plane vs host path
    for a KV-cache-sized tensor handoff (ISSUE 3 bench satellite).

    device plane  — pin + same-process resolve + unpin (what the serve
                    prefill→decode handoff pays): zero payload copies.
    host path     — serialize (device_get → out-of-band buffer) →
                    payload bytes → deserialize → device_put: what every
                    cross-task device array paid before the plane.

    Emits ONE JSON line.
    """
    jax = _require_tpu("--device-handoff")
    import jax.numpy as jnp

    from ray_tpu._private import device_objects, serialization

    # KV-cache-sized working set: 16 layers x (k, v), 64 MiB in bf16.
    layers, shape, dtype = 16, (8, 1024, 128), jnp.bfloat16
    kv = [(jnp.ones(shape, dtype), jnp.ones(shape, dtype))
          for _ in range(layers)]
    total_bytes = sum(int(k.nbytes) + int(v.nbytes) for k, v in kv)
    jax.block_until_ready(kv)
    iters = 20

    t0 = time.perf_counter()
    for _ in range(iters):
        out = device_objects.local_handoff("bench-handoff", kv)
    assert out[0][0] is kv[0][0], "device plane must hand over live arrays"
    dt_plane = (time.perf_counter() - t0) / iters

    t0 = time.perf_counter()
    for _ in range(iters):
        restored = []
        for k, v in kv:
            sk, sv = serialization.serialize(k), serialization.serialize(v)
            restored.append(
                (serialization.deserialize(sk.meta, sk.to_bytes())[1],
                 serialization.deserialize(sv.meta, sv.to_bytes())[1]))
        jax.block_until_ready(restored)
    dt_host = (time.perf_counter() - t0) / iters

    gbps_host = total_bytes / dt_host / 2**30
    stats = device_objects.registry().stats()
    d = jax.devices()[0]
    print(json.dumps({
        "metric": "device_handoff_speedup_vs_host_path",
        "value": round(dt_host / dt_plane, 1) if dt_plane > 0 else 0.0,
        "unit": "x",
        "vs_baseline": round(dt_host / dt_plane, 1) if dt_plane > 0 else 0.0,
        "extra": {
            "device": {"platform": d.platform, "kind": d.device_kind,
                       "count": len(jax.devices())},
            "payload_bytes": total_bytes,
            "layers": layers,
            "device_plane_ms": round(dt_plane * 1000, 4),
            "host_path_ms": round(dt_host * 1000, 4),
            "host_path_gib_per_s": round(gbps_host, 3),
            "plane_counters": stats["counters"],
        }}))
    return 0


def serve_disagg_main():
    """Disaggregated-serving bench: 2 prefill + 2 decode replica pools
    under one router on a local cluster, concurrent streams with
    repeated prompts so the prefix cache and the device-plane KV
    handoff both light up.

    Emits ONE JSON line — tokens/s, TTFT p50/p99, the decode pool's
    per-route KV counters (which route the prefill→decode handoff
    actually took), prefix-cache hit rate.

    Every replica leases one chip, so the host needs four; the driver
    only makes the weights and stays off the chips.
    """
    import threading

    from ray_tpu._private import accelerator

    chips = accelerator.detect_tpu_chip_count()
    if chips < 4:
        sys.exit(f"bench: --serve-disagg measures four TPU-leased replicas "
                 f"(2 prefill + 2 decode, one chip each) and this host has "
                 f"{chips} TPU chip(s); run it on the chips (no CPU "
                 f"stand-in)")
    import jax
    import jax.numpy as jnp
    import numpy as np

    jax.config.update("jax_platforms", "cpu")  # the replicas hold the chips

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models.llama import LlamaConfig, LlamaModel
    from ray_tpu.serve.llm_disagg import deploy_disagg

    cfg = LlamaConfig(vocab_size=32000, d_model=1024, n_layers=8,
                      n_heads=16, n_kv_heads=8, d_ff=4096,
                      max_seq_len=1024, dtype=jnp.bfloat16)
    max_len, max_new, prompt_len = 512, 64, 64
    n_requests, max_batch = 32, 8
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    on_chip = {"num_cpus": 0, "resources": {"TPU": 1}}

    ray_tpu.init(num_cpus=4)
    try:
        h = deploy_disagg(cfg, params, prefill_replicas=2,
                          decode_replicas=2, max_batch=max_batch,
                          max_len=max_len,
                          prefill_actor_options=on_chip,
                          decode_actor_options=on_chip)
        rng = np.random.default_rng(0)
        distinct = [list(map(int, rng.integers(1, cfg.vocab_size,
                                               size=prompt_len)))
                    for _ in range(4)]
        # Warmup outside the timed window: compiles the prefill buckets
        # and the decode step on every replica's first touch (several
        # concurrent streams so the picker reaches all four replicas).
        warm = [threading.Thread(target=lambda: list(h.stream(
            {"prompt_tokens": distinct[0], "max_new_tokens": 4})))
            for _ in range(4)]
        for t in warm:
            t.start()
        for t in warm:
            t.join(timeout=300)
        ttfts: list = []
        counts: list = []
        lock = threading.Lock()

        def run(i):
            p = distinct[i % len(distinct)]  # repeats → prefix-cache hits
            t0 = time.perf_counter()
            first, n = None, 0
            for _tok in h.stream({"prompt_tokens": p,
                                  "max_new_tokens": max_new}):
                if first is None:
                    first = time.perf_counter() - t0
                n += 1
            with lock:
                ttfts.append(first if first is not None else 0.0)
                counts.append(n)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(n_requests)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        wall = time.perf_counter() - t0
        total = sum(counts)
        pm = h.pool_metrics()
        routes: dict = {}
        for m in pm["decode"]:
            for k, v in (m.get("plane_counters") or {}).items():
                routes[k] = routes.get(k, 0) + int(v)
        hits = sum(m.get("prefix_cache_hits", 0) for m in pm["prefill"])
        misses = sum(m.get("prefix_cache_misses", 0)
                     for m in pm["prefill"])
        router_stats = dict(h.stats)
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()
    srt = sorted(ttfts)
    pick = lambda q: srt[min(len(srt) - 1,  # noqa: E731
                             int(q * len(srt)))] if srt else 0.0
    tps = round(total / wall, 1) if wall > 0 else 0.0
    print(json.dumps({
        "metric": "serve_disagg_tokens_per_s",
        "value": tps,
        "unit": "tokens/s",
        "vs_baseline": tps,
        "extra": {
            "tpu_chips": chips,
            "prefill_replicas": 2, "decode_replicas": 2,
            "requests": n_requests, "completed": len(counts),
            "prompt_len": prompt_len, "max_new_tokens": max_new,
            "total_generated": total, "wall_s": round(wall, 2),
            "ttft_p50_ms": round(pick(0.5) * 1e3, 1),
            "ttft_p99_ms": round(pick(0.99) * 1e3, 1),
            "kv_route_counters": {
                k: routes.get(k, 0)
                for k in ("in_process", "collective", "host_fallback",
                          "evacuated_in", "evacuated_out")},
            "prefix_cache_hit_rate": round(hits / (hits + misses), 3)
                                     if hits + misses else 0.0,
            "router_stats": router_stats,
        }}))
    return 0


def actor_churn_main():
    """Actor-churn microbench (ISSUE 18 bench satellite): the native
    control plane's two hot state machines, end-to-end over real
    sockets with ZERO Python in the hot path.

    Phase A — actor creations/s: a raw-socket driver pipelines stamped
    RegisterActor frames at a real GcsServer (RAY_TPU_NATIVE_CONTROL=1)
    whose node is a sim-mode native lease plane acting as the mock
    raylet, so the full RegisterActor -> CreateActor -> ActorReady
    ladder runs C++-to-C++. Target: >=1000 creations/s (the Python
    control plane measures ~26/s on this ladder).

    Phase B — lease-grant p99: sequential RequestWorkerLease round
    trips against a native lease plane backed by a real raylet_core.

    Phase C — grant/return task cycles at full pipeline WHILE a second
    driver churns actors concurrently: the 10k tasks/s floor must hold
    under churn.

    Emits ONE JSON line and writes BENCH_ACTOR_CHURN.json.
    """
    import asyncio
    import socket
    import tempfile
    import threading

    os.environ["RAY_TPU_NATIVE_CONTROL"] = "1"
    from ray_tpu._private import native_fastpath, rpc
    from ray_tpu._private.native_lease_plane import RayletLeasePlane
    from ray_tpu._private.native_raylet_core import RayletResourceCore

    if not native_fastpath.available():
        print(json.dumps({
            "metric": "actor_churn_creations_per_s", "value": 0.0,
            "unit": "actors/s", "vs_baseline": 0.0,
            "extra": {"error": "native fastpath unavailable"}}))
        return 0

    from ray_tpu._private.config import Config
    from ray_tpu._private.gcs import GcsServer

    n_actors = int(os.environ.get("RAY_TPU_BENCH_CHURN_N", "2000"))
    n_lat = int(os.environ.get("RAY_TPU_BENCH_CHURN_LAT_N", "500"))
    task_secs = float(os.environ.get("RAY_TPU_BENCH_CHURN_TASK_S", "2.0"))

    def req(seq, method, payload):
        body = rpc.pack([rpc.MSG_REQUEST, seq, method, payload])
        return len(body).to_bytes(4, "big") + body

    def read_frame(f):
        hdr = f.read(4)
        if len(hdr) != 4:
            raise RuntimeError("bench: connection closed mid-frame")
        body = f.read(int.from_bytes(hdr, "big"))
        env = rpc.unpack(body)
        if env[0] == rpc.MSG_ERROR:
            raise RuntimeError(f"bench: server error: {env[3]!r}")
        return env

    def churn(host, port, sid, prefix, n, window=256):
        """Pipelined stamped RegisterActor stream; returns ack count."""
        sk = socket.create_connection((host, port), timeout=30)
        try:
            sk.settimeout(30)
            f = sk.makefile("rb")
            next_send, acked = 0, 0
            while acked < n:
                while next_send < n and next_send - acked < window:
                    i = next_send
                    sk.sendall(req(i + 1, "RegisterActor", {
                        "actor_id": f"{prefix}{i}", "spec": b"s",
                        "max_restarts": 0, "_session": sid,
                        "_rseq": i + 1, "_acked": 0}))
                    next_send += 1
                env = read_frame(f)
                assert env[3].get("ok"), env
                acked += 1
            return acked
        finally:
            sk.close()

    # ---- GCS on a background loop; heartbeat timeout effectively off
    # (this measures the plane, not failure detection) ----
    cfg = Config()
    cfg.num_heartbeats_timeout = 10**6
    loop = asyncio.new_event_loop()
    loop_thread = threading.Thread(target=loop.run_forever, daemon=True)
    loop_thread.start()
    gcs = GcsServer(config=cfg, persistence_path=os.path.join(
        tempfile.mkdtemp(prefix="bench-churn-"), "gcs_state"))
    host, port = asyncio.run_coroutine_threadsafe(
        gcs.start(), loop).result(timeout=60)
    assert gcs._actor_plane is not None, \
        "actor plane must install for the churn bench"

    # ---- mock raylet: sim-mode lease plane on a client pump ----
    rpump = native_fastpath.FastPump()
    sim = RayletLeasePlane(rpump, inject_token=9)
    sim.set_sim(True)
    sim.install()
    conn_id = rpump.connect(host, port)
    node_id = "benchnode" + "0" * 23
    rpump.send(conn_id, rpc.pack(
        [rpc.MSG_REQUEST, 1, "RegisterNode", {
            "host": "127.0.0.1", "node_id": node_id, "raylet_port": 47001,
            "total_resources": {"CPU": 10000.0},
            "_session": "bench-raylet", "_rseq": 1, "_acked": 0}])[:])
    deadline = time.time() + 30
    registered = False
    while time.time() < deadline and not registered:
        ev = rpump.next(1.0)
        if ev and ev[0] == native_fastpath.EV_FRAME:
            env = rpc.unpack(ev[2])
            registered = env[1] == 1 and env[3].get("ok")
    assert registered, "mock raylet failed to register its node"

    error = None
    creations_per_s = 0.0
    lat_ms = []
    tasks_per_s = 0.0
    churn2_done = 0
    handled = fallthrough = deduped = 0
    try:
        # ---- phase A: actor creations/s over the full native ladder ----
        t0 = time.perf_counter()
        churn(host, port, "bench-drv", "ba", n_actors)
        # Acks cover registration; the ladder is done when RegisterActor
        # AND ActorReady were both handled natively for every actor.
        deadline = time.time() + 60
        while time.time() < deadline:
            handled, _, _ = gcs._actor_plane.counters()
            if handled >= 2 * n_actors:
                break
            rpump.drain()
            time.sleep(0.001)
        wall_a = time.perf_counter() - t0
        handled, fallthrough, deduped = gcs._actor_plane.counters()
        assert handled >= 2 * n_actors, \
            f"ladder stalled: handled={handled} want>={2 * n_actors}"
        creations_per_s = n_actors / wall_a

        # ---- dedicated raylet for lease phases ----
        lpump = native_fastpath.FastPump()
        rcore = RayletResourceCore({"CPU": 64.0})
        plane = RayletLeasePlane(lpump, inject_token=7, rcore=rcore)
        plane.set_node(node_id)
        plane.set_gate(True)
        plane.install()
        lport = lpump.listen("127.0.0.1", 0)
        workers = {f"w{i}": ("127.0.0.1", 21000 + i, 22000 + i)
                   for i in range(48)}
        for wid, (whost, wport, wfp) in workers.items():
            plane.push(wid, whost, wport, wfp)

        lsk = socket.create_connection(("127.0.0.1", lport), timeout=30)
        lsk.settimeout(30)
        lf = lsk.makefile("rb")
        lease_shape = {"resources": {"CPU": 1.0}, "strategy": None,
                       "placement_group": "", "pg_bundle_index": -1,
                       "hops": 0}
        rseq = [0]

        def lease_req(payload):
            rseq[0] += 1
            stamped = dict(payload)
            stamped.update({"_session": "bench-lease", "_rseq": rseq[0],
                            "_acked": 0})
            return req(rseq[0], "RequestWorkerLease"
                       if "resources" in payload else "ReturnWorker",
                       stamped)

        # ---- phase B: sequential grant round trips -> p50/p99 ----
        for _ in range(n_lat):
            t = time.perf_counter()
            lsk.sendall(lease_req(lease_shape))
            grant = read_frame(lf)[3]
            lat_ms.append((time.perf_counter() - t) * 1e3)
            assert grant.get("granted"), grant
            lsk.sendall(lease_req({"lease_id": grant["lease_id"],
                                   "kill": False}))
            read_frame(lf)
            w = grant["worker_id"]
            plane.push(w, *workers[w])

        # ---- phase C: pipelined grant/return cycles under churn ----
        churn_err = []

        def churn2():
            try:
                n = churn(host, port, "bench-drv2", "bc", n_actors)
            except Exception as e:  # surfaced below
                churn_err.append(e)
                n = 0
            return n

        churn_thread = threading.Thread(target=churn2, daemon=True)
        churn_thread.start()
        batch = 32
        cycles = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < task_secs:
            grants = []
            for _ in range(batch):
                lsk.sendall(lease_req(lease_shape))
            for _ in range(batch):
                g = read_frame(lf)[3]
                assert g.get("granted"), g
                grants.append((g["lease_id"], g["worker_id"]))
            for lease_id, _ in grants:
                lsk.sendall(lease_req({"lease_id": lease_id,
                                       "kill": False}))
            for _ in range(batch):
                read_frame(lf)
            for _, wid in grants:
                plane.push(wid, *workers[wid])
            cycles += batch
        tasks_per_s = cycles / (time.perf_counter() - t0)
        churn_thread.join(timeout=120)
        if churn_err:
            raise churn_err[0]
        churn2_done = n_actors

        # Wait for the churn2 ladders to finish (ActorReady lags the
        # last RegisterActor ack) so the reported totals cover BOTH
        # churn phases, then re-sample.
        deadline = time.time() + 60
        while time.time() < deadline:
            handled, fallthrough, deduped = gcs._actor_plane.counters()
            if handled >= 2 * (n_actors + churn2_done):
                break
            rpump.drain()
            time.sleep(0.001)

        assert plane.proto_errors() == 0
        assert gcs._actor_plane.proto_errors() == 0
        lsk.close()
        plane.close()
        lpump.close()
        rcore.close()
    except Exception as e:
        error = f"{type(e).__name__}: {e}"
    finally:
        sim.close()
        rpump.close()
        try:
            asyncio.run_coroutine_threadsafe(gcs.stop(), loop).result(30)
        except Exception:
            pass
        loop.call_soon_threadsafe(loop.stop)
        loop_thread.join(timeout=10)

    lat_sorted = sorted(lat_ms) or [0.0]

    def pct(p):
        return lat_sorted[min(len(lat_sorted) - 1,
                              int(p * len(lat_sorted)))]

    rec = {
        "metric": "actor_churn_creations_per_s",
        "value": round(creations_per_s, 1),
        "unit": "actors/s",
        # North star: >=1000 native actor creations/s (~40x the ~26/s
        # Python control-plane ladder).
        "vs_baseline": round(creations_per_s / 1000.0, 2),
        "extra": {
            "actors_created": n_actors,
            "lease_grant_p50_ms": round(pct(0.50), 4),
            "lease_grant_p99_ms": round(pct(0.99), 4),
            "lease_grants_timed": len(lat_ms),
            "tasks_per_s_under_churn": round(tasks_per_s, 1),
            "tasks_floor": 10000,
            "concurrent_churn_actors": churn2_done,
            "native_handled_total": handled,
            "native_fallthrough_total": fallthrough,
            "deduped_requests_total": deduped,
        }}
    if error is not None:
        rec["extra"]["error"] = error
    print(json.dumps(rec))
    # Smoke runs (tiny N) set RAY_TPU_BENCH_CHURN_ARTIFACT=0 so they
    # never clobber a full-scale capture.
    if error is None and os.environ.get(
            "RAY_TPU_BENCH_CHURN_ARTIFACT", "1") != "0":
        with open(os.path.join(_REPO_ROOT, "BENCH_ACTOR_CHURN.json"),
                  "w") as f:
            json.dump(rec, f, indent=2)
            f.write("\n")
    return 0 if error is None else 1


def control_soak_main():
    """Control-plane chaos soak (ISSUE 19 tentpole): certify the
    default-on native control plane under the faults it now owns.

    A real GcsServer (native actor plane installed) serves two fake
    raylets; node2's link runs through a seeded NetChaos proxy. The
    soak drives two waves of actor churn:

      Wave 1 (flap leg)    — NetChaos flaps node2's link while actors
                             churn: in-flight creates park on SUSPECT,
                             replay after re-registration, and the
                             raylet reply caches dedup — no forks.
      Wave 2 (preempt leg) — node2 is preempted mid-wave (NodePreempter
                             kill path: raylet gone, then the death
                             certificate via NotifyNodeDead) while a
                             native lease plane sustains pipelined
                             grant/return cycles; every orphaned
                             creation fails over to the survivor.

    Hard assertions (non-zero exit on any violation):
      * every churned actor ends ALIVE (zero lost),
      * per-actor executions <= 1 + restarts (zero forked/duplicated),
      * node2 recorded >= 1 suspect recovery (the flaps really bit),
      * grant/return cycles/s >= floor (RAY_TPU_SOAK_FLOOR, def 10000),
      * zero proto errors, zero divergence-breaker trips.

    Emits ONE JSON line; writes BENCH_CONTROL_SOAK.json
    unless RAY_TPU_BENCH_SOAK_ARTIFACT=0 (smoke runs).
    """
    import asyncio
    import socket
    import tempfile
    import threading

    os.environ["RAY_TPU_NATIVE_CONTROL"] = "1"
    from ray_tpu._private import native_fastpath, rpc
    from ray_tpu._private.native_lease_plane import RayletLeasePlane
    from ray_tpu._private.native_raylet_core import RayletResourceCore
    from ray_tpu.test_utils import NetChaos

    if not native_fastpath.available():
        print(json.dumps({
            "metric": "control_soak_cycles_per_s", "value": 0.0,
            "unit": "cycles/s", "vs_baseline": 0.0,
            "extra": {"error": "native fastpath unavailable"}}))
        return 0

    from ray_tpu._private.config import Config
    from ray_tpu._private.gcs import ACTOR_ALIVE, GcsServer

    n_wave = int(os.environ.get("RAY_TPU_SOAK_N", "400"))
    task_secs = float(os.environ.get("RAY_TPU_SOAK_TASK_S", "2.0"))
    n_flaps = int(os.environ.get("RAY_TPU_SOAK_FLAPS", "3"))
    floor = float(os.environ.get("RAY_TPU_SOAK_FLOOR", "10000"))

    def req(seq, method, payload):
        body = rpc.pack([rpc.MSG_REQUEST, seq, method, payload])
        return len(body).to_bytes(4, "big") + body

    def read_frame(f):
        hdr = f.read(4)
        if len(hdr) != 4:
            raise RuntimeError("soak: connection closed mid-frame")
        body = f.read(int.from_bytes(hdr, "big"))
        env = rpc.unpack(body)
        if env[0] == rpc.MSG_ERROR:
            raise RuntimeError(f"soak: server error: {env[3]!r}")
        return env

    def churn(host, port, sid, prefix, n, window=64):
        """Pipelined stamped RegisterActor stream (max_restarts=1: one
        failover budget per actor for the preemption leg)."""
        sk = socket.create_connection((host, port), timeout=30)
        try:
            sk.settimeout(60)
            f = sk.makefile("rb")
            next_send, acked = 0, 0
            while acked < n:
                while next_send < n and next_send - acked < window:
                    i = next_send
                    sk.sendall(req(i + 1, "RegisterActor", {
                        "actor_id": f"{prefix}{i}", "spec": b"s",
                        "max_restarts": 1, "_session": sid,
                        "_rseq": i + 1, "_acked": 0}))
                    next_send += 1
                env = read_frame(f)
                assert env[3].get("ok"), env
                acked += 1
            return acked
        finally:
            sk.close()

    def rpc_once(host, port, method, payload):
        sk = socket.create_connection((host, port), timeout=30)
        try:
            p = dict(payload)
            p.update({"_session": f"soak-{method}", "_rseq": 1,
                      "_acked": 0})
            sk.sendall(req(1, method, p))
            sk.settimeout(30)
            return read_frame(sk.makefile("rb"))[3]
        finally:
            sk.close()

    # ---- GCS on a background loop; heartbeat policing effectively off
    # so every fault in this soak is explicitly injected ----
    cfg = Config()
    cfg.num_heartbeats_timeout = 10**6
    loop = asyncio.new_event_loop()
    loop_thread = threading.Thread(target=loop.run_forever, daemon=True)
    loop_thread.start()
    gcs = GcsServer(config=cfg, persistence_path=os.path.join(
        tempfile.mkdtemp(prefix="bench-soak-"), "gcs_state"))
    host, port = asyncio.run_coroutine_threadsafe(
        gcs.start(), loop).result(timeout=60)
    assert gcs._actor_plane is not None, \
        "actor plane must install for the control soak"

    chaos = NetChaos(seed=19).start()
    n1, n2 = "f1" * 16, "f2" * 16
    execs = {}  # actor_id -> real CreateActor executions (both nodes)
    boxes = {}  # node_id -> {"sess": session, "dead": bool}

    async def fake_raylet(rhost, rport, node_id):
        """connect_session raylet: counts CreateActor executions and
        auto-ActorReadys, re-registers on every rebind (the real
        raylet's _gcs_handshake)."""
        box = {"sess": None, "dead": False}
        reg = {"host": "127.0.0.1", "node_id": node_id,
               "raylet_port": 47001,
               "total_resources": {"CPU": 100000.0}}

        def on_create(conn, payload):
            aid = payload["actor_id"]
            execs[aid] = execs.get(aid, 0) + 1

            async def ready():
                try:
                    await box["sess"].call("ActorReady", {
                        "actor_id": aid,
                        "address": ["127.0.0.1", 47002]})
                except Exception:
                    pass  # session died (kill leg): failover re-drives
            if not box["dead"]:
                asyncio.get_running_loop().create_task(ready())
            return {"ok": True}

        async def handshake(conn):
            await conn.call("RegisterNode", reg, timeout=10)

        sess = await rpc.connect_session(
            rhost, rport, handlers={"CreateActor": on_create},
            name=f"soak-raylet-{node_id[:2]}", on_reconnect=handshake)
        box["sess"] = sess
        r = await sess.call("RegisterNode", reg)
        assert r["ok"]
        boxes[node_id] = box

    phost, pport = chaos.link("n2", host, port)
    asyncio.run_coroutine_threadsafe(
        fake_raylet(host, port, n1), loop).result(30)
    asyncio.run_coroutine_threadsafe(
        fake_raylet(phost, pport, n2), loop).result(30)

    error = None
    cycles_per_s = 0.0
    alive = lost = forked = 0
    suspect_recoveries = flaps_done = 0
    handled = fallthrough = deduped = 0
    stale_epoch = proto = degraded = trips = 0
    lsk = plane = lpump = rcore = None
    all_ids = [f"s1-{i}" for i in range(n_wave)] + \
              [f"s2-{i}" for i in range(n_wave)]
    try:
        # ---- wave 1: churn while NetChaos flaps node2's link ----
        chaos_err = []

        def flapper():
            nonlocal flaps_done
            try:
                for _ in range(n_flaps):
                    time.sleep(0.15)
                    chaos.flap("n2", 0.35)
                    flaps_done += 1
                    time.sleep(0.25)
            except Exception as e:
                chaos_err.append(e)

        flap_thread = threading.Thread(target=flapper, daemon=True)
        flap_thread.start()
        churn(host, port, "soak-w1", "s1-", n_wave)
        flap_thread.join(timeout=120)
        if chaos_err:
            raise chaos_err[0]

        deadline = time.time() + 120
        while time.time() < deadline:
            if all(gcs.actors.get(a, {}).get("state") == ACTOR_ALIVE
                   for a in all_ids[:n_wave]):
                break
            time.sleep(0.05)
        # The flaps must have bitten: SUSPECT promotion on conn loss,
        # recovery on re-registration.
        deadline = time.time() + 30
        while time.time() < deadline:
            suspect_recoveries = gcs.nodes[n2].suspect_recoveries
            if suspect_recoveries >= 1:
                break
            time.sleep(0.05)

        # ---- wave 2: preempt node2 mid-churn while a native lease
        # plane sustains pipelined grant/return cycles ----
        kill_err = []

        def preempt_n2():
            try:
                # NodePreempter's kill path: the raylet process goes
                # away first, then the death certificate lands.
                box = boxes[n2]
                box["dead"] = True
                asyncio.run_coroutine_threadsafe(
                    box["sess"].close(), loop).result(15)
                rpc_once(host, port, "NotifyNodeDead",
                         {"node_id": n2, "reason": "soak preemption"})
            except Exception as e:
                kill_err.append(e)

        churn_err = []

        def churn2():
            try:
                churn(host, port, "soak-w2", "s2-", n_wave)
            except Exception as e:
                churn_err.append(e)

        churn_thread = threading.Thread(target=churn2, daemon=True)
        churn_thread.start()
        killer = threading.Timer(0.2, preempt_n2)
        killer.start()

        lpump = native_fastpath.FastPump()
        rcore = RayletResourceCore({"CPU": 64.0})
        plane = RayletLeasePlane(lpump, inject_token=7, rcore=rcore)
        plane.set_node("soaklease" + "0" * 23)
        plane.set_gate(True)
        plane.install()
        lport = lpump.listen("127.0.0.1", 0)
        workers = {f"w{i}": ("127.0.0.1", 21000 + i, 22000 + i)
                   for i in range(48)}
        for wid, waddr in workers.items():
            plane.push(wid, *waddr)
        lsk = socket.create_connection(("127.0.0.1", lport), timeout=30)
        lsk.settimeout(30)
        lf = lsk.makefile("rb")
        lease_shape = {"resources": {"CPU": 1.0}, "strategy": None,
                       "placement_group": "", "pg_bundle_index": -1,
                       "hops": 0}
        rseq = [0]

        def lease_req(payload):
            rseq[0] += 1
            stamped = dict(payload)
            stamped.update({"_session": "soak-lease", "_rseq": rseq[0],
                            "_acked": 0})
            return req(rseq[0], "RequestWorkerLease"
                       if "resources" in payload else "ReturnWorker",
                       stamped)

        batch = 32
        cycles = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < task_secs:
            grants = []
            for _ in range(batch):
                lsk.sendall(lease_req(lease_shape))
            for _ in range(batch):
                g = read_frame(lf)[3]
                assert g.get("granted"), g
                grants.append((g["lease_id"], g["worker_id"]))
            for lease_id, _ in grants:
                lsk.sendall(lease_req({"lease_id": lease_id,
                                       "kill": False}))
            for _ in range(batch):
                read_frame(lf)
            for _, wid in grants:
                plane.push(wid, *workers[wid])
            cycles += batch
        cycles_per_s = cycles / (time.perf_counter() - t0)

        churn_thread.join(timeout=120)
        killer.join(timeout=60)
        if churn_err:
            raise churn_err[0]
        if kill_err:
            raise kill_err[0]

        # ---- settle: every actor from both waves must end ALIVE ----
        deadline = time.time() + 180
        while time.time() < deadline:
            alive = sum(
                1 for a in all_ids
                if gcs.actors.get(a, {}).get("state") == ACTOR_ALIVE)
            if alive == len(all_ids):
                break
            time.sleep(0.05)

        lost = len(all_ids) - alive
        forked = sum(
            1 for a in all_ids
            if execs.get(a, 0) >
            1 + gcs.actors.get(a, {}).get("restarts", 0))
        handled, fallthrough, deduped = gcs._actor_plane.counters()
        stale_epoch = gcs._actor_plane.stale_epoch_total()
        proto = gcs._actor_plane.proto_errors()
        degraded = gcs._actor_plane.degraded_total()
        trips = gcs._native_divergence_trips
        assert plane.proto_errors() == 0

        violations = []
        if lost:
            violations.append(f"{lost} actor(s) not ALIVE (lost)")
        if forked:
            violations.append(f"{forked} actor(s) forked/duplicated")
        if suspect_recoveries < 1:
            violations.append("no suspect recovery recorded")
        if cycles_per_s < floor:
            violations.append(
                f"cycles/s {cycles_per_s:.0f} under floor {floor:.0f}")
        if proto:
            violations.append(f"{proto} proto error(s)")
        if trips or gcs._native_degraded_reason:
            violations.append("divergence breaker tripped: "
                              + gcs._native_degraded_reason)
        if violations:
            raise AssertionError("; ".join(violations))
    except Exception as e:
        error = f"{type(e).__name__}: {e}"
    finally:
        for closer in (lambda: lsk.close(), lambda: plane.close(),
                       lambda: lpump.close(), lambda: rcore.close()):
            try:
                closer()
            except Exception:
                pass
        for box in boxes.values():
            try:
                if box.get("sess") is not None:
                    asyncio.run_coroutine_threadsafe(
                        box["sess"].close(), loop).result(10)
            except Exception:
                pass
        try:
            asyncio.run_coroutine_threadsafe(gcs.stop(), loop).result(30)
        except Exception:
            pass
        loop.call_soon_threadsafe(loop.stop)
        loop_thread.join(timeout=10)
        chaos.stop()

    rec = {
        "metric": "control_soak_cycles_per_s",
        "value": round(cycles_per_s, 1),
        "unit": "cycles/s",
        # North star: the 10k grant/return cycles/s floor holds while
        # the control plane rides out flaps and a preemption.
        "vs_baseline": round(cycles_per_s / floor, 2) if floor else 0.0,
        "extra": {
            "actors_churned": len(all_ids),
            "actors_alive": alive,
            "lost": lost,
            "forked": forked,
            "suspect_recoveries": suspect_recoveries,
            "flaps": flaps_done,
            "preempted_node": n2[:8],
            "cycles_floor": floor,
            "executions_total": sum(execs.values()),
            "native_handled_total": handled,
            "native_fallthrough_total": fallthrough,
            "deduped_requests_total": deduped,
            "stale_epoch_rejections_total": stale_epoch,
            "native_degraded_total": degraded,
            "divergence_trips_total": trips,
        }}
    if error is not None:
        rec["extra"]["error"] = error
    print(json.dumps(rec))
    # Smoke runs set RAY_TPU_BENCH_SOAK_ARTIFACT=0 so they never
    # clobber a full-scale capture.
    if error is None and os.environ.get(
            "RAY_TPU_BENCH_SOAK_ARTIFACT", "1") != "0":
        with open(os.path.join(_REPO_ROOT, "BENCH_CONTROL_SOAK.json"),
                  "w") as f:
            json.dump(rec, f, indent=2)
            f.write("\n")
    return 0 if error is None else 1


def scale_chaos_main():
    """Wide-cluster chaos certification (ISSUE 20 release gate).

    A simulated 256-node, 4-tenant cluster under seeded hostility: the
    GCS carries a fake-node cluster view at width plus a small
    live-socket core of fake raylets (one behind a flapping NetChaos
    proxy), while every tenant churns actors stamped with its job id.
    Spot kills land throughout, and ONE mid-run GCS restart exercises
    streaming recovery on a workload-sized persisted table.

    Hard assertions (non-zero exit on any violation):
      * zero lost / zero forked actors across all tenants,
      * the flapped node recorded >= 1 suspect recovery,
      * time-to-first-grant after the GCS restart strictly less than
        the full-table replay time (streaming recovery observable) and
        the `recovering` flag flips off within the run,
      * every tenant's lease-grant share >= 0.5x fair share, with the
        raylet starvation counter at 0,
      * zero native proto errors / divergence-breaker trips.

    The whole chaos schedule (flap offsets/durations, kill times) is
    drawn from ONE recorded seed, so a run is reproducible bit-for-bit
    at the schedule level. Emits ONE JSON line; writes
    BENCH_SCALE_CHAOS.json unless RAY_TPU_BENCH_SCALE_ARTIFACT=0.
    """
    import asyncio
    import random
    import socket
    import tempfile
    import threading

    os.environ["RAY_TPU_NATIVE_CONTROL"] = "1"
    from ray_tpu._private import rpc
    from ray_tpu._private.common import NodeInfo
    from ray_tpu._private.config import Config
    from ray_tpu._private.gcs import ACTOR_ALIVE, ACTOR_DEAD, GcsServer
    from ray_tpu._private.native_raylet_core import RayletResourceCore
    from ray_tpu._private.raylet import Raylet
    from ray_tpu.test_utils import NetChaos, scale_chaos_schedule

    sim_nodes = int(os.environ.get("RAY_TPU_SCALE_NODES", "256"))
    tenants = int(os.environ.get("RAY_TPU_SCALE_TENANTS", "4"))
    n_per_tenant = int(os.environ.get("RAY_TPU_SCALE_N", "150"))
    seed = int(os.environ.get("RAY_TPU_SCALE_SEED", "20"))
    n_flaps = int(os.environ.get("RAY_TPU_SCALE_FLAPS", "4"))
    backlog_rows = int(os.environ.get("RAY_TPU_SCALE_BACKLOG", "4000"))
    lease_target = int(os.environ.get("RAY_TPU_SCALE_LEASES", "2000"))

    chaos_schedule = scale_chaos_schedule(seed, n_flaps)
    flap_schedule = chaos_schedule["flaps"]
    kill_offsets = chaos_schedule["kills"]

    def req(seq, method, payload):
        body = rpc.pack([rpc.MSG_REQUEST, seq, method, payload])
        return len(body).to_bytes(4, "big") + body

    def read_frame(f):
        hdr = f.read(4)
        if len(hdr) != 4:
            raise RuntimeError("scale-chaos: connection closed mid-frame")
        body = f.read(int.from_bytes(hdr, "big"))
        env = rpc.unpack(body)
        if env[0] == rpc.MSG_ERROR:
            raise RuntimeError(f"scale-chaos: server error: {env[3]!r}")
        return env

    def churn(host, port, sid, prefix, n, job_id, window=64):
        """Pipelined stamped RegisterActor stream for one tenant."""
        sk = socket.create_connection((host, port), timeout=30)
        try:
            sk.settimeout(60)
            f = sk.makefile("rb")
            next_send, acked = 0, 0
            while acked < n:
                while next_send < n and next_send - acked < window:
                    i = next_send
                    # max_restarts=4: an actor can be failed over by
                    # BOTH spot kills plus flap-window churn.
                    sk.sendall(req(i + 1, "RegisterActor", {
                        "actor_id": f"{prefix}{i}", "spec": b"s",
                        "max_restarts": 4, "job_id": job_id,
                        "_session": sid, "_rseq": i + 1, "_acked": 0}))
                    next_send += 1
                env = read_frame(f)
                assert env[3].get("ok"), env
                acked += 1
            return acked
        finally:
            sk.close()

    def rpc_once(host, port, method, payload, sid=None):
        sk = socket.create_connection((host, port), timeout=30)
        try:
            p = dict(payload)
            p.update({"_session": sid or f"scale-{method}", "_rseq": 1,
                      "_acked": 0})
            sk.sendall(req(1, method, p))
            sk.settimeout(30)
            return read_frame(sk.makefile("rb"))[3]
        finally:
            sk.close()

    # ---- GCS on a background loop; heartbeat policing off so every
    # fault is the schedule's, not the wall clock's ----
    cfg = Config()
    cfg.num_heartbeats_timeout = 10**6
    state_path = os.path.join(tempfile.mkdtemp(prefix="bench-scale-"),
                              "gcs_state")
    loop = asyncio.new_event_loop()
    loop_thread = threading.Thread(target=loop.run_forever, daemon=True)
    loop_thread.start()

    def on_loop(coro, timeout=60):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(timeout)

    gcs = GcsServer(config=cfg, persistence_path=state_path)
    host, port = on_loop(gcs.start())

    live_ids = [f"l{i}" * 8 for i in range(1, 5)]  # 4 live-socket raylets
    n1, n2, n3, n4 = live_ids

    async def inject_sim_nodes(g, count):
        # The fake-node width: real rows in the node table (answered,
        # published, persisted, replayed at restart) that take no
        # placements (zero capacity).
        for i in range(count):
            nid = f"sim{i:04d}" + "0" * 25
            g.nodes[nid] = NodeInfo(
                node_id=nid, host="10.0.0.1", raylet_port=50000,
                total_resources={"CPU": 0.0},
                available_resources={"CPU": 0.0})
            if g.native_sched is not None:
                g.native_sched.update_node(nid, total={"CPU": 0.0},
                                           available={"CPU": 0.0},
                                           alive=True)
        g.mark_dirty(("nodes",))

    on_loop(inject_sim_nodes(gcs, max(0, sim_nodes - len(live_ids))))

    chaos = NetChaos(seed=seed).start()
    execs = {}  # actor_id -> real CreateActor executions across raylets
    boxes = {}  # node_id -> {"sess", "dead"}
    pub_seen = [0]  # fanout notifies delivered to subscribed raylets

    async def fake_raylet(rhost, rport, node_id):
        box = {"sess": None, "dead": False}
        reg = {"host": "127.0.0.1", "node_id": node_id,
               "raylet_port": 47001,
               "total_resources": {"CPU": 100000.0}}

        def on_create(conn, payload):
            aid = payload["actor_id"]
            if box["dead"]:
                # Spot-killed, its session not torn down yet: a frame
                # that still lands here executes nothing.
                return {"ok": True}
            execs[aid] = execs.get(aid, 0) + 1

            async def ready():
                try:
                    await box["sess"].call("ActorReady", {
                        "actor_id": aid,
                        "address": ["127.0.0.1", 47002]})
                except Exception:
                    pass  # session died (kill leg): failover re-drives
            asyncio.get_running_loop().create_task(ready())
            return {"ok": True}

        def on_publish(conn, payload):
            pub_seen[0] += 1  # fanout deliveries landing on this raylet

        async def handshake(conn):
            await conn.call("RegisterNode", reg, timeout=10)
            # Real raylets watch the state channels; subscribing here
            # puts the churn waves through the fanout pumps so the gate
            # certifies them under chaos, not an idle path.
            await conn.call("Subscribe",
                            {"channels": ["ACTOR", "NODE"]}, timeout=10)

        sess = await rpc.connect_session(
            rhost, rport,
            handlers={"CreateActor": on_create, "Publish": on_publish},
            name=f"scale-raylet-{node_id[:2]}", on_reconnect=handshake)
        box["sess"] = sess
        r = await sess.call("RegisterNode", reg)
        assert r["ok"]
        await sess.call("Subscribe", {"channels": ["ACTOR", "NODE"]})
        boxes[node_id] = box

    phost, pport = chaos.link("n2", host, port)
    on_loop(fake_raylet(host, port, n1), 30)
    on_loop(fake_raylet(phost, pport, n2), 30)
    on_loop(fake_raylet(host, port, n3), 30)
    on_loop(fake_raylet(host, port, n4), 30)

    def spot_kill(node_id):
        # NodePreempter's kill path: raylet gone, then the certificate.
        box = boxes[node_id]
        box["dead"] = True
        on_loop(box["sess"].close(), 15)
        rpc_once(host, port, "NotifyNodeDead",
                 {"node_id": node_id, "reason": "scale-chaos spot kill"})

    def run_wave(wave, gcs_now, flap_slice):
        """One churn wave: all tenants churn concurrently while the
        seeded flaps bite n2's link and one spot kill lands."""
        errs = []

        def tenant_churn(k):
            try:
                churn(host, port, f"scale-{wave}-t{k}", f"t{k}{wave}-",
                      n_per_tenant, f"tenant-{k}")
            except Exception as e:
                errs.append(e)

        def flapper():
            try:
                for off, dur in flap_slice:
                    time.sleep(off)
                    chaos.flap("n2", dur)
            except Exception as e:
                errs.append(e)

        kill_target = n3 if wave == "a" else n4

        def killer():
            try:
                time.sleep(kill_offsets[0 if wave == "a" else 1])
                spot_kill(kill_target)
            except Exception as e:
                errs.append(e)

        threads = [threading.Thread(target=tenant_churn, args=(k,),
                                    daemon=True) for k in range(tenants)]
        threads.append(threading.Thread(target=flapper, daemon=True))
        threads.append(threading.Thread(target=killer, daemon=True))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        if errs:
            raise errs[0]
        ids = [f"t{k}{wave}-{i}" for k in range(tenants)
               for i in range(n_per_tenant)]
        deadline = time.time() + 180
        while time.time() < deadline:
            if all(gcs_now.actors.get(a, {}).get("state") == ACTOR_ALIVE
                   for a in ids):
                break
            time.sleep(0.05)
        return ids

    async def inject_backlog(g, count):
        # Workload-sized settled rows: the bulk a blocking replay would
        # have to apply before answering, and exactly what the recovery
        # stream defers.
        for i in range(count):
            aid = f"bk-{i}"
            g.actors[aid] = {
                "actor_id": aid, "state": ACTOR_DEAD, "address": None,
                "node_id": None, "class_name": "Backlog", "name": "",
                "namespace": "default", "job_id": "tenant-0",
                "restarts": 0, "max_restarts": 0, "death_cause": "exit",
                "spec": b"", "dead_worker_ids": set()}
        g.mark_dirty(("actors",))

    async def fairness_leg():
        """Real raylet queue policy (Raylet._pump_pending_leases +
        _acquire over a native RayletResourceCore) under a 4-tenant
        contention pattern: tenant-0 floods, the rest submit steadily.
        Returns per-tenant grants, queue-wait percentiles, starvation."""
        rcore = RayletResourceCore({"CPU": 32.0})
        grants = {f"tenant-{k}": 0 for k in range(tenants)}
        waits = []
        done = asyncio.get_running_loop().create_future()

        import collections

        class H:
            pass

        h = H()
        h.node_id = "scalefair"
        h.pending_leases = collections.deque()
        h._lease_rr_last = ""
        h._lease_starvation = 0
        h._lease_grants_by_job = {}
        h._starvation_threshold_s = 5.0
        h._native_sched = None
        h.cluster_view = {}
        h.available = {}
        h.rcore = rcore
        h._lease_seq = 0
        h._acquire = Raylet._acquire.__get__(h)
        h._pump_pending_leases = Raylet._pump_pending_leases.__get__(h)
        h._pick_spillback = Raylet._pick_spillback.__get__(h)

        async def grant_lease(lease_id, resources, pg_id, bundle_index,
                              received_at=None):
            return {"granted": True, "lease_id": lease_id,
                    "received_at": received_at}

        h._grant_lease = grant_lease
        total = [0]

        # Closed-loop tenants: each keeps a bounded window outstanding
        # and refills as grants land. Tenant-0 is the flood — its
        # window is ~8x a steady tenant's, so strict FIFO would let it
        # monopolize the pool; the round-robin lanes must not. Windows
        # (rather than enqueueing every lease upfront) keep the queue
        # depth ~constant, so waits measure scheduling, not the drain
        # time of an ever-growing backlog.
        remaining = {"tenant-0": lease_target}
        window = {"tenant-0": 256}
        for k in range(1, tenants):
            remaining[f"tenant-{k}"] = lease_target // 2
            window[f"tenant-{k}"] = 32
        outstanding = dict.fromkeys(remaining, 0)

        def on_granted(fut):
            if fut.cancelled():
                return
            r = fut.result()
            if not r.get("granted"):
                return
            job = fut._job
            grants[job] += 1
            waits.append(time.time() - r["received_at"])
            total[0] += 1
            outstanding[job] -= 1
            if total[0] >= lease_target and not done.done():
                done.set_result(None)
                return
            if not done.done():
                refill(job)
            # ~1ms hold, then the release re-pumps the queue — a worker
            # pool of 32 sustained against the contended queue.
            loop.call_later(0.001, release, r["lease_id"])

        closed = [False]

        def release(lease_id):
            # call_later releases still in flight when the leg finishes
            # must not touch the destroyed native pool.
            if closed[0]:
                return
            rcore.release(lease_id)
            h._pump_pending_leases()

        def refill(job):
            while outstanding[job] < window[job] and remaining[job]:
                remaining[job] -= 1
                outstanding[job] += 1
                fut = loop.create_future()
                fut._job = job
                fut.add_done_callback(on_granted)
                h.pending_leases.append(
                    ({"CPU": 1.0}, "", -1, fut, False, time.time(), job))

        # The flood lands FIRST, then the steady tenants.
        for job in remaining:
            refill(job)
        h._pump_pending_leases()
        await asyncio.wait_for(done, 120)
        for item in list(h.pending_leases):  # cancel the remainder
            if not item[3].done():
                item[3].cancel()
        h.pending_leases.clear()
        waits_ms = sorted(w * 1000 for w in waits)

        def pct(p):
            return round(waits_ms[min(len(waits_ms) - 1,
                                      int(p * len(waits_ms)))], 3)

        stats = {"grants_by_tenant": dict(grants),
                 "grants_total": total[0],
                 "lease_p50_ms": pct(0.50), "lease_p99_ms": pct(0.99),
                 "starvation": h._lease_starvation}
        closed[0] = True
        rcore.close()
        return stats

    error = None
    all_ids = []
    lost = forked = 0
    suspect_recoveries = 0
    fairness = {}
    recovery = {}
    fanout = {}
    proto = trips = 0
    gcs2 = gcs
    try:
        # ---- wave A: 4-tenant churn + flaps + spot kill (n3) ----
        all_ids += run_wave("a", gcs, flap_schedule[:n_flaps // 2])
        deadline = time.time() + 30
        while time.time() < deadline:
            suspect_recoveries = gcs.nodes[n2].suspect_recoveries
            if suspect_recoveries >= 1:
                break
            time.sleep(0.05)

        # ---- mid-run GCS restart: streaming recovery at width ----
        on_loop(inject_backlog(gcs, backlog_rows))
        pre_restart_recoveries = suspect_recoveries
        fanout_pre = dict(gcs._fanout_stats)  # wave-A pump counters
        on_loop(gcs.stop())  # final flush + compact
        gcs2 = GcsServer(config=cfg, persistence_path=state_path)
        on_loop(gcs2.start(port=port))  # same port: sessions reconnect
        t_up = time.perf_counter()
        # First grant: a fresh control-plane answer (RegisterActor ack)
        # racing the recovery stream.
        r = rpc_once(host, port, "RegisterActor", {
            "actor_id": "probe-0", "spec": b"s", "max_restarts": 4,
            "job_id": "tenant-0"}, sid="scale-probe")
        assert r.get("ok"), r
        first_grant_ms = (time.perf_counter() - t_up) * 1000
        all_ids.append("probe-0")
        recovered_deadline = time.time() + 60
        while time.time() < recovered_deadline and gcs2.recovering:
            time.sleep(0.001)
        recovered = not gcs2.recovering
        rs = gcs2._recovery_stats
        # From the GCS's own record, after the fact: a poll of the flag
        # misses a stream that drains between start() and the first read.
        recovering_observed = rs["streamed_rows"] > 0
        full_replay_ms = round(rs["prefix_ms"] + rs["stream_ms"], 3)
        recovery = {
            "prefix_rows": rs["prefix_rows"],
            "streamed_rows": rs["streamed_rows"],
            "prefix_ms": round(rs["prefix_ms"], 3),
            "stream_ms": round(rs["stream_ms"], 3),
            "full_replay_ms": full_replay_ms,
            "first_grant_ms": round(first_grant_ms, 3),
            "recovering_observed": recovering_observed,
            "recovered": recovered,
        }

        # ---- wave B: churn resumes against the recovered GCS, flaps
        # continue, second spot kill (n4) ----
        all_ids += run_wave("b", gcs2, flap_schedule[n_flaps // 2:])
        suspect_recoveries = pre_restart_recoveries + \
            gcs2.nodes[n2].suspect_recoveries

        # ---- fair-share lease leg: 4 tenants against one contended
        # raylet queue (real pump policy over the native rcore) ----
        fairness = on_loop(fairness_leg(), 180)
        fair_share = fairness["grants_total"] / tenants
        fairness["fair_ratios"] = {
            j: round(g / fair_share, 3)
            for j, g in fairness["grants_by_tenant"].items()}
        fairness["min_ratio"] = min(fairness["fair_ratios"].values())

        # ---- settle + invariants ----
        deadline = time.time() + 180
        while time.time() < deadline:
            alive = sum(
                1 for a in all_ids
                if gcs2.actors.get(a, {}).get("state") == ACTOR_ALIVE)
            if alive == len(all_ids):
                break
            time.sleep(0.05)
        lost = len(all_ids) - alive
        forked = sum(
            1 for a in all_ids
            if execs.get(a, 0) >
            1 + gcs2.actors.get(a, {}).get("restarts", 0))
        fanout = {  # both GCS incarnations drove the pumps; sum them
            k: (max(fanout_pre.get(k, 0), v) if k == "max_depth"
                else fanout_pre.get(k, 0) + v)
            for k, v in gcs2._fanout_stats.items()}
        fanout["delivered_to_raylets"] = pub_seen[0]
        if gcs2._actor_plane is not None:
            proto = gcs2._actor_plane.proto_errors()
        trips = gcs2._native_divergence_trips

        violations = []
        if lost:
            violations.append(f"{lost} actor(s) not ALIVE (lost)")
        if forked:
            violations.append(f"{forked} actor(s) forked/duplicated")
        if suspect_recoveries < 1:
            violations.append("no suspect recovery recorded")
        if not recovering_observed:
            violations.append(
                "recovery streamed no rows (`recovering` never on)")
        if not recovered:
            violations.append("recovering flag never flipped off")
        if first_grant_ms >= full_replay_ms:
            violations.append(
                f"first grant {first_grant_ms:.1f}ms not faster than "
                f"full replay {full_replay_ms:.1f}ms")
        if fairness["min_ratio"] < 0.5:
            violations.append(
                f"tenant below fair share: {fairness['fair_ratios']}")
        if fairness["starvation"]:
            violations.append(
                f"{fairness['starvation']} starved grant(s)")
        if not (fanout["sent"] or fanout["native_batches"]):
            violations.append("fanout carried no traffic")
        if proto:
            violations.append(f"{proto} proto error(s)")
        if trips or gcs2._native_degraded_reason:
            violations.append("divergence breaker tripped: "
                              + gcs2._native_degraded_reason)
        if violations:
            raise AssertionError("; ".join(violations))
    except Exception as e:
        error = f"{type(e).__name__}: {e}"
    finally:
        for box in boxes.values():
            try:
                if box.get("sess") is not None:
                    asyncio.run_coroutine_threadsafe(
                        box["sess"].close(), loop).result(10)
            except Exception:
                pass
        try:
            asyncio.run_coroutine_threadsafe(gcs2.stop(), loop).result(30)
        except Exception:
            pass
        loop.call_soon_threadsafe(loop.stop)
        loop_thread.join(timeout=10)
        chaos.stop()

    rec = {
        "metric": "scale_chaos_lease_p99_ms",
        "value": fairness.get("lease_p99_ms", 0.0),
        "unit": "ms",
        # North star: scheduler p99 under 4-tenant contention at the
        # 256-node certified envelope (ROADMAP "scale number that
        # survives a hostile network").
        "vs_baseline": round(
            250.0 / fairness["lease_p99_ms"], 2) if
        fairness.get("lease_p99_ms") else 0.0,
        "extra": {
            "sim_nodes": sim_nodes,
            "live_nodes": len(live_ids),
            "tenants": tenants,
            "chaos_schedule": chaos_schedule,
            "actors_churned": len(all_ids),
            "lost": lost,
            "forked": forked,
            "suspect_recoveries": suspect_recoveries,
            "spot_kills": 2,
            "recovery": recovery,
            "fairness": fairness,
            "fanout": fanout,
            "divergence_trips_total": trips,
        }}
    if error is not None:
        rec["extra"]["error"] = error
    print(json.dumps(rec))
    # Smoke runs set RAY_TPU_BENCH_SCALE_ARTIFACT=0 so they never
    # clobber a full-scale capture.
    if error is None and os.environ.get(
            "RAY_TPU_BENCH_SCALE_ARTIFACT", "1") != "0":
        with open(os.path.join(_REPO_ROOT, "BENCH_SCALE_CHAOS.json"),
                  "w") as f:
            json.dump(rec, f, indent=2)
            f.write("\n")
    return 0 if error is None else 1


if __name__ == "__main__":
    if _DEVICE_HANDOFF_MODE:
        sys.exit(device_handoff_main())
    if _SERVE_DISAGG_MODE:
        sys.exit(serve_disagg_main())
    if _ACTOR_CHURN_MODE:
        sys.exit(actor_churn_main())
    if _CONTROL_SOAK_MODE:
        sys.exit(control_soak_main())
    if _SCALE_CHAOS_MODE:
        sys.exit(scale_chaos_main())
    main()
