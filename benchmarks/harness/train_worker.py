"""The train cell's loop, run by `JaxTrainer` in the worker that holds the
chip: the framework's own SPMD step (`make_train_step` /
`shard_train_step` / `init_sharded_state`) on the configuration's model,
fed by a host thread that packs documents one batch ahead.  The benchmark's
spans are taken around the compiled call; nothing in `ray_tpu/` changes.
"""

from __future__ import annotations

import math
import queue
import threading
import time

from benchmarks.harness import cluster, loader, trace_reduce, traffic
from benchmarks.harness.replica import JAX_SEED_MASK

TRACE_AFTER_STEPS = 3
TRACE_STEPS = 10


def build_program(family, cfg, mesh, optimizer_spec: dict):
    """The step by the repo's own SPMD path (as chip_smoke.py builds it)
    over the family's model and loss."""
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    from ray_tpu.train.spmd import make_train_step, shard_train_step

    if optimizer_spec["name"] != "adafactor":
        raise ValueError(f"unknown optimizer {optimizer_spec['name']!r}")
    model = family.model(cfg)
    optimizer = optax.adafactor(float(optimizer_spec["learning_rate"]))

    def init_fn(key):
        return model.init(key, jnp.zeros((1, 8), jnp.int32))

    def loss_fn(params, batch):
        inp, tgt = batch
        return family.loss(model.apply(params, inp), tgt)

    step = make_train_step(loss_fn, optimizer)
    batch_spec = (P(("dp", "fsdp"), None), P(("dp", "fsdp"), None))
    return init_fn, optimizer, batch_spec, \
        lambda specs: shard_train_step(step, mesh, specs, batch_spec)


def init_state(mesh, init_fn, optimizer, key):
    """The sharded state from the seed, laid out by the repo's own rules.
    `train.spmd.init_sharded_state` does the same in one program that
    closes over its arguments; the key would then be a constant of the
    program and every new seed would miss the compile cache.  Here the key
    is an argument, and the parameters come from their own jitted program
    (`init_params`), which the reference check calls again after the
    window without compiling anything."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel import TRANSFORMER_RULES
    from ray_tpu.train.spmd import TrainState, state_specs_from_rules

    def build(key):
        params = init_fn(key)
        return TrainState(params, optimizer.init(params),
                          jnp.zeros((), jnp.int32))

    specs = state_specs_from_rules(jax.eval_shape(build, key),
                                   TRANSFORMER_RULES)
    on_mesh = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda spec: NamedSharding(mesh, spec), tree,
        is_leaf=lambda x: isinstance(x, P))
    init_params = jax.jit(init_fn, out_shardings=on_mesh(specs.params))
    params = init_params(key)
    opt_state = jax.jit(optimizer.init,
                        out_shardings=on_mesh(specs.opt_state))(params)
    return TrainState(params, opt_state, jnp.zeros((), jnp.int32)), specs, \
        init_params


class Prefetcher:
    """A host thread that packs the next batch and puts it on the device
    while the current step runs (one batch ahead)."""

    def __init__(self, packer, rows: int, shardings):
        self._packer, self._rows, self._shardings = packer, rows, shardings
        self._q: queue.Queue = queue.Queue(maxsize=1)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._fill, daemon=True,
                                        name="bench-prefetch")
        self._thread.start()

    def _fill(self) -> None:
        import jax

        while not self._stop.is_set():
            data = self._packer.batch(self._rows)
            batch = jax.device_put((data[:, :-1], data[:, 1:]),
                                   self._shardings)
            while not self._stop.is_set():
                try:
                    self._q.put((data, batch), timeout=0.1)
                    break
                except queue.Full:
                    continue

    def next(self):
        return self._q.get(timeout=120.0)

    def close(self) -> None:
        self._stop.set()
        self._thread.join(5.0)


def train_loop(config: dict) -> None:
    """train_loop_per_worker.  Reports one dict a step through
    `train.report`, and a last one with everything the driver needs."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu import train
    from ray_tpu.parallel import MeshConfig, make_mesh

    cluster.uncap_compile_cache()
    compiles = cluster.CompileCounter()
    who = cluster.device_report()
    sizes, tr, seed = config["sizes"], config["train"], config["seed"]
    mix = config["traffic"]
    seconds, trace = config["seconds"], config["trace"]
    family = loader.load_family(config["family"], config["root"])
    cfg = family.program_config(sizes, attention=tr["attention"],
                                remat=True, remat_policy=tr["remat_policy"])
    devices = jax.devices()[:config["chips"]]
    mesh = make_mesh(MeshConfig(fsdp=len(devices)), devices=devices)
    init_fn, optimizer, batch_spec, sharded_step = build_program(
        family, cfg, mesh, tr["optimizer"])
    key = jax.random.PRNGKey(seed & JAX_SEED_MASK)
    t0 = time.monotonic()
    state, specs, init_params = init_state(mesh, init_fn, optimizer, key)
    jax.block_until_ready(state)
    init_state_s = time.monotonic() - t0
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), batch_spec,
        is_leaf=lambda x: isinstance(x, P))
    rows, seq = int(tr["sequences_per_step"]), int(mix["seq_len"])
    packer = traffic.DocumentPacker(mix, seed, sizes["vocab_size"])
    feed = Prefetcher(packer, rows, shardings)
    try:
        first_data, batch = feed.next()
        t0 = time.monotonic()
        lowered = sharded_step(specs).lower(state, batch)
        kernel_calls = lowered.as_text().count("tpu_custom_call")
        step = lowered.compile()
        compile_s = time.monotonic() - t0
        mem = step.memory_analysis()

        # Two steps outside the window: the first gives the loss at the
        # seeded weights (checked against ln V and the reference).
        losses = []
        for _ in range(2):
            state, metrics = step(state, batch)
            losses.append(float(jax.block_until_ready(metrics["loss"])))
            _data, batch = feed.next()
        compiles_at_open = compiles.count
        t_open = time.monotonic()
        train.report({"kind": "window_open", "t": t_open})

        steps, tracer = [], trace_reduce.Tracer()
        while True:
            t_a = time.monotonic()
            if trace and len(steps) == TRACE_AFTER_STEPS:
                tracer.start()
                t_a = time.monotonic()
            t_b = time.monotonic()
            state, metrics = step(state, batch)
            t_c = time.monotonic()
            loss = float(jax.block_until_ready(metrics["loss"]))
            t_d = time.monotonic()
            _data, batch = feed.next()
            t_e = time.monotonic()
            train.report({"kind": "step", "step": len(steps) + 1,
                          "loss": loss})
            t_f = time.monotonic()
            steps.append({"start": t_a, "dispatch": t_b, "dispatched": t_c,
                          "done": t_d, "got_batch": t_e, "reported": t_f,
                          "loss": loss,
                          "traced": tracer.running})
            if tracer.running and \
                    len(steps) == TRACE_AFTER_STEPS + TRACE_STEPS:
                tracer.stop()
            if t_d - t_open >= seconds:
                break
        t_close = steps[-1]["done"]
        if tracer.running:
            tracer.stop()
        compiles_in_window = compiles.count - compiles_at_open
    finally:
        feed.close()
    memory_peak = cluster.memory_peak_bytes()

    reduced = tracer.reduce(lambda off: _gap_labeller(steps, off)) \
        if tracer.started else None
    if reduced is not None:
        reduced["traced_steps"] = TRACE_STEPS

    # The reference, outside the window, on the weights the seed gives
    # (the trained state goes first: both do not fit beside the step).
    del state, step, batch
    reference_loss = None
    if config["reference_rows"]:
        params = jax.block_until_ready(init_params(key))
        n = int(config["reference_rows"])
        reference_loss = family.reference.mean_token_loss(
            params, sizes, first_data[:n, :-1], first_data[:n, 1:])
        model_loss = _first_rows_loss(family, cfg, params, first_data[:n])
        del params
    else:
        model_loss = None

    train.report({
        "kind": "final", "worker": who, "steps": steps, "losses": losses,
        "t_open": t_open, "t_close": t_close, "rows": rows, "seq": seq,
        "init_state_s": init_state_s, "compile_s": compile_s,
        "kernel_calls": kernel_calls, "documents": packer.documents,
        "compiled_bytes": {
            "arguments": int(mem.argument_size_in_bytes),
            "outputs": int(mem.output_size_in_bytes),
            "temporaries": int(mem.temp_size_in_bytes),
            "aliased": int(mem.alias_size_in_bytes)},
        "memory_peak_bytes": memory_peak,
        "compiles_in_window": compiles_in_window,
        "reference_loss": reference_loss, "model_loss": model_loss,
        "trace": reduced,
        "log_vocab": math.log(sizes["vocab_size"])})


def _first_rows_loss(family, cfg, params, data) -> float:
    """The system's own loss (its model, its loss function, its dtype) on
    the rows the reference saw: the step's loss is the mean over the whole
    batch, the reference is given only some rows."""
    import jax

    model = family.model(cfg)
    fn = jax.jit(lambda p, x, y: family.loss(model.apply(p, x), y))
    return float(fn(params, data[:, :-1], data[:, 1:]))


def _gap_labeller(steps: list, off_ns: float):
    def label(t0, t1):
        mid = (t0 + t1) / 2 + off_ns
        for s in steps:
            if s["done"] * 1e9 <= mid < s["reported"] * 1e9:
                return "between steps: next batch + train.report"
            if s["start"] * 1e9 <= mid < s["dispatched"] * 1e9:
                return "step dispatch on the host"
            if s["dispatched"] * 1e9 <= mid < s["done"] * 1e9:
                return "inside a step (device waits within the program)"
        return "unattributed"

    return label
