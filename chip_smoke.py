"""The main path, once, on the chip: driver -> GCS/raylet -> a worker that
leased TPU -> the library -> JAX on the device.

    python3 chip_smoke.py            # one chip: serve phase, then train phase
    python3 chip_smoke.py --chips 4  # one trainer worker over four chips

Llama-3-8B at its published widths (only `n_layers` is cut; the depth is
printed), random weights made from a seed INSIDE the worker that holds
the chip.  The driver never initialises a JAX backend.  One JSON line per
phase; the last line of stdout is the verdict the caller reads:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any failing phase, a missing chip, or a device that is not a TPU makes
the script exit non-zero without that line.  There is no option that
relaxes the device check: the phases are functions of the model config so
that tests can call them at TINY widths on the CPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import re
import sys
import threading
import time
from typing import Any, Callable, NamedTuple

import ray_tpu
from ray_tpu import serve
from ray_tpu._private import accelerator
from ray_tpu.models.llama import LLAMA3_8B, LlamaConfig
from ray_tpu.serve.llm import LLMServer
from ray_tpu.train import JaxTrainer, ScalingConfig

SEED = 0

# ---- serve phase sizes ----------------------------------------------------
# Weights are ~0.44 GB a layer plus 2.1 GB of embedding and head in bf16:
# 16 layers are 9.1 GB, which leaves room on a 16 GB chip for the prefill
# logits (W x bucket x 128256 f32), the KV pools and the compiler's temps.
SERVE_LAYERS = 16
ENGINE_KWARGS = dict(max_batch=8, max_len=256, page_size=64, decode_chunk=8)
PROMPT_LENGTHS = (9, 23, 41, 64)
MAX_NEW_TOKENS = 32
# Greedy engine vs greedy Generator: tokens must be equal up to the first
# divergence, and there the reference's own logits must call it a tie —
# the engine's token within two bf16 steps (2 x 2^-5 at |logit| in [4, 8),
# where the top logits of a 128k vocabulary lie) of the reference's.
LOGIT_TIE_TOLERANCE = 0.0625

# ---- train phase sizes ----------------------------------------------------
# bf16 params + bf16 grads are 0.87 GB a layer plus 4.2 GB for the
# 128256 x 4096 embedding and head; full AdamW state (8 more bytes a
# parameter) does not fit beside them, so the optimiser is Adafactor
# (factored second moments), and every layer is rematerialised in full.
TRAIN_LAYERS = 12
TRAIN_SEQ = 2048
TRAIN_BATCH = 1
TRAIN_STEPS = 5
TRAIN_OVERRIDES = dict(attention="flash", remat=True, remat_policy="full")
OPTIMIZER = "adafactor(1e-3)"

# ---- four chips (--chips 4) -----------------------------------------------
# The whole published depth: 16 GB of bf16 weights alone, more than one
# chip has, sharded over the four by TRANSFORMER_RULES.  Compiled for a
# described v5e:2x2, fsdp=2 x tp=2 needs 9.7 GB a device and fsdp=4
# 15.4 GB (the gathered weights and the logits of a whole sequence are
# not divided by an fsdp axis), so the mesh is the former.
FOUR_CHIP_LAYERS = 32
FOUR_CHIP_MESH = dict(fsdp=2, tp=2)
FOUR_CHIP_BATCH = 4
FOUR_CHIP_STEPS = 3
COMPARE_LAYERS = 2          # a depth one device and four can both run
FIRST_LOSS_TOLERANCE = 0.05  # |loss on 1 device - loss on 4|, loss ~ ln(V)

SCHEDULE_DEADLINE_S = 5.0
REPLICA_READY_DEADLINE_S = 420.0
SERVE_CALL_DEADLINE_S = 600.0
TRAIN_DEADLINE_S = 900.0
FOUR_CHIP_DEADLINE_S = 2400.0
EXIT_DEADLINE_S = 60.0


class SmokeFailure(RuntimeError):
    pass


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def smoke_config(base: LlamaConfig, n_layers: int, **overrides) -> LlamaConfig:
    return dataclasses.replace(base, n_layers=n_layers, **overrides)


def cache_entries() -> int:
    d = accelerator.compile_cache_dir()
    return len(os.listdir(d)) if os.path.isdir(d) else 0


def require_tpu_resource(chips: int) -> None:
    """A phase that cannot be scheduled fails here, in seconds, with what
    the node detected — never as an actor pending for ever."""
    deadline = time.monotonic() + SCHEDULE_DEADLINE_S
    while True:
        total = ray_tpu.cluster_resources()
        if total.get("TPU", 0) >= chips:
            return
        if time.monotonic() > deadline:
            raise SmokeFailure(
                f"no TPU device to lease: the phase needs TPU: {chips} and "
                f"this node offers {total} (detect_tpu_chip_count() = "
                f"{accelerator.detect_tpu_chip_count()} from "
                f"TPU_VISIBLE_CHIPS, /dev/accel*, /dev/vfio/[0-9]*)")
        time.sleep(0.2)


def wait_for_exit(pid: int) -> float:
    """Seconds until process `pid` (on this host) is gone."""
    t0 = time.monotonic()
    while os.path.exists(f"/proc/{pid}"):
        if time.monotonic() - t0 > EXIT_DEADLINE_S:
            raise SmokeFailure(f"worker {pid} still alive after "
                               f"{EXIT_DEADLINE_S:.0f}s; the chip is held")
        time.sleep(0.05)
    return time.monotonic() - t0


def _device_report() -> dict:
    """Run INSIDE the lease-holder: what jax and the runtime say there."""
    import jax

    d = jax.devices()[0]
    return {"pid": os.getpid(), "platform": d.platform,
            "kind": d.device_kind, "count": len(jax.devices()),
            "pinned_platform": accelerator.pinned_platform(),
            "compile_cache_dir": jax.config.jax_compilation_cache_dir}


def _widths(cfg: LlamaConfig) -> dict:
    return dict(n_layers=cfg.n_layers, d_model=cfg.d_model,
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                d_ff=cfg.d_ff, vocab_size=cfg.vocab_size,
                dtype=cfg.dtype.__name__)


def _kernel_calls(lowered_text: str) -> int:
    return lowered_text.count("tpu_custom_call")


STATE_MOVES = ("copy", "copy-start", "slice-start")


def state_moves(text: str, state) -> dict:
    """What the compiled program `text` does to buffers of the shape of a
    leaf of `state`, inside its loops and outside them: {"loop": {...},
    "outside": {...}}, each with the count of `copy` (a change of
    layout), `copy-start` and `slice-start` (a move to another memory
    space, whole or in slices) whose result has such a shape, and
    `moved`, the copy-starts and the slices together in units of a whole
    leaf.  A decode step should find its state where the last one left
    it: every one of these is a pool read and written for nothing."""
    import jax

    hlo = {"bfloat16": "bf16", "float32": "f32"}
    shapes = {f"{hlo[x.dtype.name]}[{','.join(map(str, x.shape))}]":
              math.prod(x.shape) for x in jax.tree_util.tree_leaves(state)}
    computations, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            name = head.group(1)
            computations[name] = []
        elif name is not None:
            computations[name].append(line)
    calls = {n: set(re.findall(
        r"(?:body|condition|calls|to_apply)=%([\w.\-]+)", "\n".join(ls)))
        for n, ls in computations.items()}
    in_loop = set(re.findall(r"body=%([\w.\-]+)", text))
    grew = True
    while grew:
        more = set().union(*(calls[n] for n in in_loop if n in calls))
        grew = not more <= in_loop
        in_loop |= more
    counts = {where: dict.fromkeys(STATE_MOVES + ("moved",), 0)
              for where in ("loop", "outside")}
    for n, lines in computations.items():
        tally = counts["loop" if n in in_loop else "outside"]
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ("
                         + "|".join(STATE_MOVES) + r")\(", line)
            if not m:
                continue
            result, op = m.groups()
            leaf = next((s for s in shapes if s in result), None)
            if leaf is None:
                continue
            tally[op] += 1
            if op == "copy-start":
                tally["moved"] += 1
            elif op == "slice-start":
                lo_hi = re.findall(r"\[(\d+):(\d+)\]",
                                   line.split("slice={")[1])
                tally["moved"] += math.prod(
                    int(b) - int(a) for a, b in lo_hi) / shapes[leaf]
    for tally in counts.values():
        tally["moved"] = round(tally["moved"], 3)
    return counts


# ===========================================================================
# serve
# ===========================================================================


class SmokeLLM(LLMServer):
    """LLMServer whose weights are made from a seed in the replica."""

    def __init__(self, cfg: LlamaConfig, seed: int, engine_kwargs: dict):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.llama import LlamaModel

        t0 = time.perf_counter()
        model = LlamaModel(cfg)
        params = jax.jit(lambda: model.init(
            jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)))()
        jax.block_until_ready(params)
        self._cfg, self._params = cfg, params
        self._init_s = time.perf_counter() - t0
        super().__init__(cfg, params, **engine_kwargs)

    def whoami(self) -> dict:
        import jax

        leaves = jax.tree_util.tree_leaves(self._params)
        return dict(_device_report(),
                    param_bytes=sum(int(x.nbytes) for x in leaves),
                    param_dtypes=sorted({str(x.dtype) for x in leaves}),
                    init_params_s=round(self._init_s, 2))

    def warmup(self, prompts: list, max_new: int) -> float:
        """Compile every program the requests below can reach: the batched
        prefill + decode chunk (all prompts at once), then the
        single-sequence prefill (one alone).  Arrival order decides which
        of the two prefills a request gets."""
        from ray_tpu.models.generate import SamplingParams

        t0 = time.perf_counter()
        sp = SamplingParams(max_new_tokens=max_new)
        for group in (prompts, prompts[:1]):
            for h in [self.engine.submit(p, sp) for p in group]:
                h.tokens()
        return time.perf_counter() - t0

    def kernels(self) -> dict:
        """tpu_custom_call sites in the engine's lowered decode program
        (the paged Pallas kernel), so a drop to interpret mode shows, and
        what the compiled program does to buffers of a pool's shape
        (`state_moves`), so a copy of the state that came back shows.
        (The program ran: compiling it again is a load from the cache.)"""
        import jax
        import jax.numpy as jnp

        eng = self.engine
        B = eng.max_batch
        shape = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
        i32 = jax.ShapeDtypeStruct((B,), jnp.int32)
        f32 = jax.ShapeDtypeStruct((B,), jnp.float32)
        lowered = eng._decode_chunk_paged.lower(
            jax.tree_util.tree_map(shape, self._params), i32, i32,
            jax.tree_util.tree_map(shape, eng._pools),
            jax.ShapeDtypeStruct(eng._tables.shape, jnp.int32),
            i32, f32, i32, f32,
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((), jnp.int32))
        return {"decode_chunk_paged": _kernel_calls(lowered.as_text()),
                "decode_chunk_paged_state_moves": state_moves(
                    lowered.compile().as_text(), eng._pools)}

    def reference(self, prompts: list, engine_tokens: list,
                  max_new: int) -> list:
        """Greedy decode of the same prompts with the plain Generator on
        the same weights; per prompt, where the engine's tokens first
        differ and how far apart the reference's logits put the two."""
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models.generate import Generator, SamplingParams

        gen = Generator(self._cfg, self._params, batch=1,
                        max_len=self.engine.max_len)
        out = []
        for prompt, got in zip(prompts, engine_tokens):
            want = gen.generate([prompt], SamplingParams(
                max_new_tokens=max_new))[0].tolist()
            n = min(len(want), len(got))
            first = next((i for i in range(n) if want[i] != got[i]), None)
            rec = {"prompt_len": len(prompt), "new_tokens": len(got),
                   "agree": n if first is None else first,
                   "first_divergence": first, "logit_gap": None}
            if first is not None:
                # Replay the shared prefix through the Generator's own
                # programs (same shapes: nothing new compiles) and read
                # the reference's logits where the two part.
                S = len(prompt)
                logits, caches = gen._prefill(
                    self._params, jnp.asarray([prompt], jnp.int32),
                    jnp.full((1,), S, jnp.int32), gen._fresh_caches())
                for i in range(first):
                    logits, caches = gen._decode(
                        self._params, jnp.asarray([want[i]], jnp.int32),
                        jnp.full((1,), S + i, jnp.int32), caches)
                row = np.asarray(logits[0], np.float32)
                rec["logit_gap"] = float(row[want[first]] - row[got[first]])
                rec["top_logit"] = float(row[want[first]])
            out.append(rec)
        return out

    def plane(self) -> dict:
        from ray_tpu._private import device_objects

        return {"counters": device_objects.counters(),
                "handoff_fallbacks": self.engine.handoff_fallbacks}


def _prompts(cfg: LlamaConfig, lengths, seed: int) -> list:
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, size=n).tolist() for n in lengths]


def serve_phase(cfg: LlamaConfig, *, engine_kwargs: dict = ENGINE_KWARGS,
                prompt_lengths=PROMPT_LENGTHS,
                max_new: int = MAX_NEW_TOKENS, seed: int = SEED) -> dict:
    """serve.run a TPU-leased LLMServer replica, stream concurrent requests
    through the handle, compare with Generator in the same replica.
    Returns the phase record; raises SmokeFailure when a check fails."""
    t_phase = time.perf_counter()
    require_tpu_resource(1)
    entries_before = cache_entries()
    deployment = serve.deployment(
        SmokeLLM, name="SmokeLLM",
        ray_actor_options={"resources": {"TPU": 1}, "max_concurrency": 8})
    try:
        handle = serve.run(deployment.bind(cfg, seed, dict(engine_kwargs)))

        def call(method, *args, deadline=SERVE_CALL_DEADLINE_S):
            return handle.options(method_name=method).remote(*args).result(
                timeout=deadline)

        who = call("whoami", deadline=REPLICA_READY_DEADLINE_S)
        t_ready = time.perf_counter() - t_phase
        prompts = _prompts(cfg, prompt_lengths, seed)
        warm_s = call("warmup", prompts, max_new)

        # Concurrent streaming requests through the Serve handle.
        streamed: list = [None] * len(prompts)
        errors: list = []

        def stream(i):
            try:
                streamed[i] = list(handle.options(stream=True).remote(
                    {"prompt_tokens": prompts[i],
                     "max_new_tokens": max_new}))
            except BaseException as e:  # noqa: BLE001 — re-raised below
                errors.append(e)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=stream, args=(i,), daemon=True)
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(SERVE_CALL_DEADLINE_S)
        stream_s = time.perf_counter() - t0
        if errors:
            raise errors[0]
        if any(t.is_alive() for t in threads) or None in streamed:
            raise SmokeFailure("a streaming request did not finish in "
                               f"{SERVE_CALL_DEADLINE_S:.0f}s")

        compared = call("reference", prompts, streamed, max_new)
        kernels = call("kernels")
        plane = call("plane")
    finally:
        serve.shutdown()
    release_s = wait_for_exit(who["pid"])

    rec = dict(
        phase="serve", worker=who, **_widths(cfg),
        engine=dict(engine_kwargs), requests=len(prompts),
        prompt_lengths=list(prompt_lengths), max_new_tokens=max_new,
        compared_with="models.generate.Generator, greedy, same replica: "
                      "token equality; at a first divergence, the "
                      "reference's logit gap between the two tokens",
        logit_tie_tolerance=LOGIT_TIE_TOLERANCE, comparison=compared,
        kernels=kernels, device_plane=plane,
        seconds=dict(replica_ready=round(t_ready, 2),
                     warmup_compile=round(warm_s, 2),
                     streams=round(stream_s, 2),
                     chip_released_after_shutdown=round(release_s, 2),
                     phase=round(time.perf_counter() - t_phase, 2)),
        compile_cache=dict(dir=who["compile_cache_dir"],
                           entries_before=entries_before,
                           entries_after=cache_entries()))
    emit(**rec)

    if who["pid"] == os.getpid():
        raise SmokeFailure("the serve phase ran in the driver process")
    for c, toks in zip(compared, streamed):
        if len(toks) != max_new:
            raise SmokeFailure(f"a stream returned {len(toks)} tokens, "
                               f"wanted {max_new}")
        if c["first_divergence"] is not None and (
                c["logit_gap"] is None
                or not 0 <= c["logit_gap"] <= LOGIT_TIE_TOLERANCE):
            raise SmokeFailure(
                f"engine and Generator part at token {c['first_divergence']} "
                f"of the prompt of length {c['prompt_len']} with a reference "
                f"logit gap of {c['logit_gap']} (> {LOGIT_TIE_TOLERANCE})")
    if plane["handoff_fallbacks"] or plane["counters"]["in_process"] == 0:
        raise SmokeFailure(
            f"prefill->decode KV handoff did not ride the device plane: "
            f"{plane}")
    if who["platform"] == "tpu" and not kernels["decode_chunk_paged"]:
        raise SmokeFailure("the decode program holds no tpu_custom_call: "
                           "the paged kernel fell to interpret mode")
    return rec


# ===========================================================================
# train
# ===========================================================================


class TrainProgram(NamedTuple):
    init_fn: Callable        # () -> params
    optimizer: Any
    build_state: Callable    # () -> TrainState (for eval_shape)
    batch_spec: Any
    sharded_step: Callable   # specs -> jitted step on the mesh


def train_program(cfg: LlamaConfig, mesh, seed: int = SEED) -> TrainProgram:
    """The train phase's step, by the repo's own SPMD path."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    from ray_tpu.models.llama import LlamaModel, cross_entropy_loss
    from ray_tpu.train.spmd import (TrainState, make_train_step,
                                    shard_train_step)

    model = LlamaModel(cfg)
    optimizer = optax.adafactor(1e-3)

    def init_fn():
        return model.init(jax.random.PRNGKey(seed),
                          jnp.zeros((1, 8), jnp.int32))

    def build_state():
        params = init_fn()
        return TrainState(params, optimizer.init(params),
                          jnp.zeros((), jnp.int32))

    def loss_fn(params, batch):
        inp, tgt = batch
        return cross_entropy_loss(model.apply(params, inp), tgt)

    step = make_train_step(loss_fn, optimizer)
    batch_spec = (P(("dp", "fsdp"), None), P(("dp", "fsdp"), None))
    return TrainProgram(
        init_fn, optimizer, build_state, batch_spec,
        lambda specs: shard_train_step(step, mesh, specs, batch_spec))


def _run_steps(cfg: LlamaConfig, devices, mesh_axes: dict, batch: int,
               seq: int, steps: int, seed: int, report) -> dict:
    """Inside the trainer worker: build the mesh over `devices`, init the
    sharded state from the seed, take `steps` steps on a fixed batch."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel import MeshConfig, TRANSFORMER_RULES, make_mesh
    from ray_tpu.train.spmd import init_sharded_state

    mesh = make_mesh(MeshConfig(**mesh_axes), devices=devices)
    prog = train_program(cfg, mesh, seed)
    t0 = time.perf_counter()
    state, specs = init_sharded_state(
        mesh, prog.init_fn, TRANSFORMER_RULES, prog.optimizer)
    jax.block_until_ready(state)
    init_s = time.perf_counter() - t0
    data = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    example = jax.device_put(
        (data[:, :-1], data[:, 1:]),
        jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                               prog.batch_spec,
                               is_leaf=lambda x: isinstance(x, P)))
    t0 = time.perf_counter()
    lowered = prog.sharded_step(specs).lower(state, example)
    kernel_calls = _kernel_calls(lowered.as_text())
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    losses, step_s, resident = [], [], None
    for i in range(steps):
        t0 = time.perf_counter()
        state, metrics = compiled(state, example)
        loss = float(jax.block_until_ready(metrics["loss"]))
        step_s.append(round(time.perf_counter() - t0, 4))
        losses.append(loss)
        report({"step": i + 1, "loss": loss})
        if i == 0:
            resident = {str(d.id): 0 for d in devices}
            for leaf in jax.tree_util.tree_leaves(state):
                for shard in leaf.addressable_shards:
                    resident[str(shard.device.id)] += int(shard.data.nbytes)
    stats = [d.memory_stats() or {} for d in devices]
    return dict(
        n_layers=cfg.n_layers, mesh={k: v for k, v in mesh.shape.items()
                                     if v > 1} or {"devices": 1},
        n_devices=len(devices), batch=batch, seq=seq, losses=losses,
        step_seconds=step_s, init_state_s=round(init_s, 2),
        compile_s=round(compile_s, 2), kernel_calls=kernel_calls,
        compiled_kernel_calls=_kernel_calls(compiled.as_text()),
        state_bytes_per_device_after_step_1=resident,
        peak_bytes_in_use=[s.get("peak_bytes_in_use") for s in stats],
        compiled_bytes_per_device=int(
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes))


def _train_loop(config: dict) -> None:
    """train_loop_per_worker: runs in the TrainWorker that leased TPU."""
    import jax

    from ray_tpu import train

    report = lambda m: train.report(dict(m, kind="step", run=run_name))  # noqa: E731
    runs = {}
    for run_name, spec in config["runs"].items():
        devices = jax.devices()[:spec["n_devices"]]
        if len(devices) != spec["n_devices"]:
            raise RuntimeError(
                f"run {run_name!r} needs {spec['n_devices']} devices, this "
                f"worker sees {len(jax.devices())}")
        runs[run_name] = _run_steps(
            spec["cfg"], devices, spec["mesh"], spec["batch"], spec["seq"],
            spec["steps"], config["seed"], report)
    train.report({"kind": "final", "worker": _device_report(), "runs": runs})


def _fit(chips: int, runs: dict, seed: int,
         deadline_s: float = TRAIN_DEADLINE_S) -> dict:
    """JaxTrainer.fit() with a deadline; returns the worker's final report."""
    require_tpu_resource(chips)
    trainer = JaxTrainer(
        _train_loop, train_loop_config={"runs": runs, "seed": seed},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     tpu_chips_per_worker=chips))
    box: dict = {}

    def fit():
        try:
            box["result"] = trainer.fit()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            box["error"] = e

    t = threading.Thread(target=fit, daemon=True)
    t.start()
    t.join(deadline_s)
    if t.is_alive():
        raise SmokeFailure(f"JaxTrainer.fit() still running after "
                           f"{deadline_s:.0f}s")
    if "error" in box:
        raise box["error"]
    final = box["result"].metrics
    if final.get("kind") != "final":
        raise SmokeFailure(f"trainer ended without its final report: {final}")
    return final


def _check_losses(name: str, run: dict) -> None:
    import math

    losses = run["losses"]
    if not losses or not all(math.isfinite(x) for x in losses):
        raise SmokeFailure(f"{name}: non-finite loss in {losses}")
    if len(losses) > 1 and not losses[-1] < losses[0]:
        raise SmokeFailure(f"{name}: loss did not fall: {losses}")


def train_phase(cfg: LlamaConfig, *, seq: int = TRAIN_SEQ,
                batch: int = TRAIN_BATCH, steps: int = TRAIN_STEPS,
                seed: int = SEED) -> dict:
    """JaxTrainer with one worker holding one chip: a few steps of the
    SPMD train step (flash attention) on a fixed seeded batch."""
    t_phase = time.perf_counter()
    entries_before = cache_entries()
    final = _fit(1, {"train": dict(cfg=cfg, n_devices=1, mesh={"fsdp": 1},
                                   batch=batch, seq=seq, steps=steps)}, seed)
    who, run = final["worker"], final["runs"]["train"]
    release_s = wait_for_exit(who["pid"])
    rec = {"phase": "train", "worker": who, **_widths(cfg),
           "attention": cfg.attention, "remat_policy": cfg.remat_policy,
           "optimizer": OPTIMIZER, **run,
           "seconds": dict(chip_released_after_fit=round(release_s, 2),
                           phase=round(time.perf_counter() - t_phase, 2)),
           "compile_cache": dict(dir=who["compile_cache_dir"],
                                 entries_before=entries_before,
                                 entries_after=cache_entries())}
    emit(**rec)
    if who["pid"] == os.getpid():
        raise SmokeFailure("the train phase ran in the driver process")
    _check_losses("train", run)
    if who["platform"] == "tpu" and cfg.attention == "flash" \
            and not run["kernel_calls"]:
        raise SmokeFailure("the train step holds no tpu_custom_call: flash "
                           "attention fell to interpret mode or a reference")
    return rec


def four_chip_phase(cfg: LlamaConfig, *, deep_layers: int = FOUR_CHIP_LAYERS,
                    compare_layers: int = COMPARE_LAYERS,
                    mesh: dict = FOUR_CHIP_MESH, batch: int = FOUR_CHIP_BATCH,
                    seq: int = TRAIN_SEQ, steps: int = FOUR_CHIP_STEPS,
                    seed: int = SEED) -> dict:
    """One trainer worker holding four chips.  First the comparison: the
    same seed and batch at a depth both can run, first-step loss on one
    device and on the four-device mesh.  Then a depth one chip could not
    hold, sharded over the four, a few steps."""
    n = 1
    for v in mesh.values():
        n *= v
    shallow = smoke_config(cfg, compare_layers)
    deep = smoke_config(cfg, deep_layers)
    one = dict(n_devices=1, mesh={"fsdp": 1}, batch=batch, seq=seq, steps=1)
    final = _fit(n, {
        "one_device": dict(one, cfg=shallow),
        "four_devices": dict(one, cfg=shallow, n_devices=n, mesh=mesh),
        "sharded": dict(cfg=deep, n_devices=n, mesh=mesh, batch=batch,
                        seq=seq, steps=steps)}, seed, FOUR_CHIP_DEADLINE_S)
    who, runs = final["worker"], final["runs"]
    wait_for_exit(who["pid"])
    delta = abs(runs["one_device"]["losses"][0]
                - runs["four_devices"]["losses"][0])
    rec = dict(phase="four_chips", worker=who, optimizer=OPTIMIZER,
               first_loss_delta=delta,
               first_loss_tolerance=FIRST_LOSS_TOLERANCE, **runs)
    emit(**rec)
    if who["pid"] == os.getpid():
        raise SmokeFailure("the four-chip phase ran in the driver process")
    if not delta <= FIRST_LOSS_TOLERANCE:
        raise SmokeFailure(f"first-step loss differs by {delta} between one "
                           f"device and {n} (> {FIRST_LOSS_TOLERANCE})")
    _check_losses("sharded", runs["sharded"])
    held = list(runs["sharded"]["state_bytes_per_device_after_step_1"]
                .values())
    if len(held) != n or min(held) == 0 or max(held) > 0.5 * sum(held):
        raise SmokeFailure(f"the state is not spread over the {n} devices: "
                           f"bytes per device {held}")
    if who["platform"] == "tpu" and not runs["sharded"]["kernel_calls"]:
        raise SmokeFailure("the sharded train step holds no tpu_custom_call")
    return rec


# ===========================================================================


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve then train on one chip (default); "
                         "4: only the sharded trainer over four chips")
    args = ap.parse_args(argv)

    emit(phase="start", driver_pid=os.getpid(), chips=args.chips,
         model="LLAMA3_8B widths, n_layers cut", seed=SEED,
         compile_cache_dir=accelerator.compile_cache_dir(),
         compile_cache_entries=cache_entries())
    ray_tpu.init()
    try:
        emit(phase="cluster", resources=ray_tpu.cluster_resources())
        if args.chips == 4:
            workers = [four_chip_phase(
                smoke_config(LLAMA3_8B, FOUR_CHIP_LAYERS,
                             **TRAIN_OVERRIDES))["worker"]]
        else:
            served = serve_phase(smoke_config(LLAMA3_8B, SERVE_LAYERS))
            trained = train_phase(smoke_config(LLAMA3_8B, TRAIN_LAYERS,
                                               **TRAIN_OVERRIDES))
            workers = [served["worker"], trained["worker"]]
            if workers[0]["pid"] == workers[1]["pid"]:
                raise SmokeFailure("both phases ran in one worker process")
    finally:
        ray_tpu.shutdown()

    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise SmokeFailure("the driver initialised a JAX backend: it must "
                           "stay off the chip")
    for w in workers:
        if w["platform"] != "tpu" or w["pinned_platform"] != "tpu":
            raise SmokeFailure(f"a phase did not run on a TPU: {w}")
    device = {k: workers[-1][k] for k in ("platform", "kind", "count")}
    if device["count"] != args.chips:
        raise SmokeFailure(f"wanted {args.chips} device(s), the worker saw "
                           f"{device['count']}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
