"""Compile the smoke's kernels and whole programs for a described v5e.

No chip is attached: the TPU compiler runs against a topology
description (`on-chip-measurement` guide, section 2, third rehearsal),
so what the chip's compiler would refuse — a misaligned block, too much
VMEM, a program over 16 GB, a kernel that cannot be partitioned — fails
here, at the widths `chip_smoke.py` runs.  Nothing executes; these say
nothing about results or times.

Everything that touches the topology happens inside fixtures and tests
(never at import): only the xdist worker that is handed this file loads
the TPU library.  Keep these cases in this one file.
"""

import os
import signal
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from ray_tpu.models.llama import LLAMA3_8B, LlamaModel  # noqa: E402
from ray_tpu.ops import attention, paged_attention  # noqa: E402

HBM_BYTES = 16 * 1024 ** 3
KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    # The TPU library installs its own SIGTERM handler when it loads, and
    # that handler prints a stack trace.  A test run that is cut by its
    # clock ends in SIGTERM; the trace would land on the line of dots the
    # run is counted by.  Keep the handler this process had.
    sigterm = signal.getsignal(signal.SIGTERM)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        signal.signal(signal.SIGTERM, sigterm)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _compile_for_the_chip(monkeypatch):
    """The default backend here is the CPU, where both kernel files
    choose interpret mode; these compiles are for the chip, at the
    chip's default matmul precision (conftest asks for "highest", which
    Mosaic refuses for bf16 operands).  The persistent cache cannot read
    a described-device entry back, so it stays off around them."""
    monkeypatch.setattr(attention, "_interpret_mode", lambda: False)
    monkeypatch.setattr(paged_attention, "_interpret_mode", lambda: False)
    from jax.experimental.compilation_cache import compilation_cache

    cache = jax.config.jax_enable_compilation_cache
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", cache)
    jax.config.update("jax_default_matmul_precision", precision)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _peak_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _qkv(one_chip, seq, hkv=32):
    q = jax.ShapeDtypeStruct((1, 32, seq, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, hkv, seq, 128), jnp.bfloat16,
                              sharding=one_chip)
    return q, kv, kv


def _flash(q, k, v):
    return attention.flash_attention(q, k, v, None, True)


@pytest.mark.parametrize("seq", [2048, 200],
                         ids=["seq2048", "bucket200_not_pow2"])
def test_flash_forward(one_chip, seq):
    compiled = jax.jit(_flash).lower(*_qkv(one_chip, seq)).compile()
    assert KERNEL in compiled.as_text()


def test_flash_forward_backward(one_chip):
    def loss(q, k, v):
        return _flash(q, k, v).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_qkv(one_chip, 2048)).compile()
    assert KERNEL in compiled.as_text()


@pytest.mark.parametrize("B, pool_pages", [(32, 385), (4, 193)],
                         ids=["chat_open_b32", "docs_closed_b4"])
def test_paged_decode_batch(one_chip, B, pool_pages):
    """The two serve cells' shapes (`benchmarks/configs/mistral-7b-v0.3-
    l16*.json`: 32/8 heads of 128, pages of 64, a table of ceil((2304 + 8)
    / 64) = 37 columns, the pool with its dummy page)."""
    H, Hkv, D, page, table_pages = 32, 8, 128, 64, 37
    S = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    pool = S((pool_pages, Hkv, page, D), jnp.bfloat16)
    compiled = jax.jit(paged_attention.paged_decode_attention_batch).lower(
        S((B, H, D), jnp.bfloat16), pool, pool,
        S((B, table_pages), jnp.int32), S((B,), jnp.int32)).compile()
    assert KERNEL in compiled.as_text()


@pytest.fixture(scope="module")
def engine_programs(one_chip):
    """The serve phase's engine at the smoke's widths and depth, built
    around parameter SHAPES (no array of that size exists here)."""
    from ray_tpu.serve.llm import LLMEngine

    cfg = chip_smoke.smoke_config(LLAMA3_8B, chip_smoke.SERVE_LAYERS)
    params = jax.eval_shape(
        lambda: LlamaModel(cfg).init(jax.random.PRNGKey(0),
                                     jnp.zeros((1, 8), jnp.int32)))
    eng = LLMEngine(cfg, params, **chip_smoke.ENGINE_KWARGS)
    yield cfg, eng, _on(one_chip, params)
    eng.shutdown()


def test_engine_decode_step(engine_programs, one_chip):
    cfg, eng, params = engine_programs
    B = eng.max_batch
    S = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    compiled = eng._decode_chunk_paged.lower(
        params, S((B,), jnp.int32), S((B,), jnp.int32),
        _on(one_chip, eng._pools), S(eng._tables.shape, jnp.int32),
        S((B,), jnp.int32), S((B,), jnp.float32), S((B,), jnp.int32),
        S((B,), jnp.float32), S((2,), jnp.uint32)).compile()
    assert KERNEL in compiled.as_text()
    assert _peak_bytes(compiled) < HBM_BYTES


def test_engine_batched_prefill(engine_programs, one_chip):
    """The engine prefills through the dense masked path over its KV
    cache (no Pallas kernel on it); what is checked is that the whole
    (W, bucket) program compiles and fits beside the weights."""
    cfg, eng, params = engine_programs
    W = eng._batch_prefill_width
    bucket = max(eng._bucket(chip_smoke.PROMPT_LENGTHS[-1]), eng.page_size)
    compiled = eng._prefill_many.lower(
        params,
        jax.ShapeDtypeStruct((W, bucket), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((W,), jnp.int32, sharding=one_chip)).compile()
    assert _peak_bytes(compiled) < HBM_BYTES


def _abstract_train(cfg, mesh, batch, seq):
    prog = chip_smoke.train_program(cfg, mesh)
    state = jax.eval_shape(prog.build_state)
    shard = lambda tree, specs: jax.tree_util.tree_map(  # noqa: E731
        lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
        tree, specs, is_leaf=lambda x: isinstance(x, P))
    from ray_tpu.train.spmd import state_specs_from_rules
    from ray_tpu.parallel import TRANSFORMER_RULES

    specs = state_specs_from_rules(state, TRANSFORMER_RULES)
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    return prog.sharded_step(specs), shard(state, specs), \
        shard((tok, tok), prog.batch_spec)


# XLA compiles a whole train step for the described chip in native code:
# 76-105 s on the 8-core sandbox beside five other workers, and it scales
# with the machine. The limit is a guard against hangs, not a budget.
_WHOLE_STEP_LIMIT = pytest.mark.time_limit(600)


@_WHOLE_STEP_LIMIT
def test_train_step_fits_one_chip(topo):
    from ray_tpu.parallel import MeshConfig, make_mesh

    cfg = chip_smoke.smoke_config(LLAMA3_8B, chip_smoke.TRAIN_LAYERS,
                                  **chip_smoke.TRAIN_OVERRIDES)
    mesh = make_mesh(MeshConfig(fsdp=1), devices=topo.devices[:1])
    step, state, batch = _abstract_train(cfg, mesh, chip_smoke.TRAIN_BATCH,
                                         chip_smoke.TRAIN_SEQ)
    compiled = step.lower(state, batch).compile()
    assert KERNEL in compiled.as_text()
    assert _peak_bytes(compiled) < HBM_BYTES


@_WHOLE_STEP_LIMIT
def test_train_step_sharded_over_four_chips(topo):
    """`chip_smoke.py --chips 4` at a cut depth: the whole 32 layers take
    three minutes to compile (done by hand; CHANGES.md has the bytes).
    What this guards is depth-independent: the flash kernel inside a
    program partitioned over four devices, and a state that is sharded."""
    from ray_tpu.parallel import MeshConfig, make_mesh

    cfg = chip_smoke.smoke_config(LLAMA3_8B, 4, **chip_smoke.TRAIN_OVERRIDES)
    mesh = make_mesh(MeshConfig(**chip_smoke.FOUR_CHIP_MESH),
                     devices=topo.devices)
    step, state, batch = _abstract_train(
        cfg, mesh, chip_smoke.FOUR_CHIP_BATCH, chip_smoke.TRAIN_SEQ)
    compiled = step.lower(state, batch).compile()
    assert KERNEL in compiled.as_text()
    assert _peak_bytes(compiled) < HBM_BYTES
    # Per-device bytes: what one device is handed of the state is a
    # quarter of the whole (norm scales and scalars replicate).
    whole = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(state))
    assert compiled.memory_analysis().argument_size_in_bytes < 0.3 * whole


# ---- the SambaY family at the widths of `phi4flash-serve-reason-closed` -----

SAMBAY_ENGINE = dict(max_batch=32, max_len=17472, page_size=64,
                     decode_chunk=8, kv_pool_tokens=303104)


def test_sambay_kernels_at_the_cells_shapes(one_chip):
    """Heads of 64 run as pairs: 40 zero-padded query heads and 10 KV
    heads of 128, scale 1/8.  The paged kernel over the one shared pool
    (4,737 pages of 64, a table of ceil((17472 + 8) / 64) = 274 columns,
    float32 queries) and the flash kernel over a 16,384-token prompt."""
    S = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    pool = S((4737, 10, 64, 128), jnp.bfloat16)
    compiled = jax.jit(lambda *a: paged_attention.paged_decode_attention_batch(
        *a, sm_scale=0.125)).lower(
        S((32, 40, 128), jnp.float32), pool, pool, S((32, 274), jnp.int32),
        S((32,), jnp.int32)).compile()
    assert KERNEL in compiled.as_text()
    qkv = S((1, 40, 16384, 128), jnp.bfloat16)
    compiled = jax.jit(lambda q, k, v: attention.flash_attention(
        q, k, v, 0.125, True)).lower(qkv, qkv, qkv).compile()
    assert KERNEL in compiled.as_text()


@pytest.mark.slow      # 45 s of a many-threaded compile: by hand, not in tier-1
@_WHOLE_STEP_LIMIT
def test_sambay_engine_programs_fit_the_chip(one_chip):
    """The cell's decode chunk (eight paged calls a step, rings and
    recurrent state carried through the scan, a count of steps a slot)
    and its largest prefill (one row of 16,384 tokens: the scan, eight
    windowed layers in blocks, flash over the full layer) at published
    widths, each beside the weights and the engine's whole state."""
    from ray_tpu.models.sambay import PHI4_MINI_FLASH, SambaYModel
    from ray_tpu.serve.llm import LLMEngine

    cfg = PHI4_MINI_FLASH
    params = jax.eval_shape(
        lambda: SambaYModel(cfg).init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 8), jnp.int32)))
    eng = LLMEngine(cfg, params, **SAMBAY_ENGINE)
    try:
        B = eng.max_batch
        S = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=one_chip)
        state = _on(one_chip, eng._pools)
        state_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                          for x in jax.tree_util.tree_leaves(eng._pools))
        decode = eng._decode_chunk_paged.lower(
            _on(one_chip, params), S((B,), jnp.int32), S((B,), jnp.int32),
            state, S(eng._tables.shape, jnp.int32), S((B,), jnp.int32),
            S((B,), jnp.float32), S((B,), jnp.int32), S((B,), jnp.float32),
            S((2,), jnp.uint32), S((B,), jnp.int32)).compile()
        assert decode.as_text().count(KERNEL) >= 8
        assert _peak_bytes(decode) < HBM_BYTES
        assert eng.family.prefill_width(16384, B) == 1
        prefill = eng._prefill_one.lower(
            _on(one_chip, params), S((1, 16384), jnp.int32),
            S((1,), jnp.int32)).compile()
        assert KERNEL in prefill.as_text()
        # (the state is not an argument of the prefill: it is resident)
        assert _peak_bytes(prefill) + state_bytes < HBM_BYTES
    finally:
        eng.shutdown()
