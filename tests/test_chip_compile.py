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

import functools
import os
import re
import signal
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from ray_tpu.models.llama import (LLAMA3_8B, LlamaConfig,  # noqa: E402
                                  LlamaModel)
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


_NOTHING = dict.fromkeys(chip_smoke.STATE_MOVES + ("moved",), 0)


def _compiled_decode_chunk(eng, params, one_chip):
    """The engine's decode chunk compiled for the chip at the engine's
    own shapes (a count of steps a slot where the family needs one)."""
    B = eng.max_batch
    S = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    steps = () if eng.family.rewinds else (S((B,), jnp.int32),)
    return eng._decode_chunk_paged.lower(
        _on(one_chip, params), S((B,), jnp.int32), S((B,), jnp.int32),
        _on(one_chip, eng._pools), S(eng._tables.shape, jnp.int32),
        S((B,), jnp.int32), S((B,), jnp.float32), S((B,), jnp.int32),
        S((B,), jnp.float32), S((2,), jnp.uint32), S((), jnp.int32),
        *steps).compile()


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


def _compiled_flash_grad(q, k, v):
    def loss(q, k, v):
        return _flash(q, k, v).astype(jnp.float32).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v).compile()


def test_flash_forward_backward(one_chip):
    compiled = _compiled_flash_grad(*_qkv(one_chip, 2048))
    assert KERNEL in compiled.as_text()


def test_flash_backward_keeps_no_score_block_in_hbm(one_chip):
    """The gradient at the train cell's shapes (`internlm2-train-packed2k`:
    4 rows of 2048 tokens, 16 heads of 128, K and V already repeated, bf16,
    causal): the forward kernel and the backward's one, and no buffer of a
    block of scores.  The scan of einsums this replaced held
    f32[4,16,512,2048] (268 MB) three times over and its bf16 copy twice:
    537 MB of temporaries; delta and lse, as rows, are all that is left."""
    B, H, S, D, block = 4, 16, 2048, 128, 512
    x = jax.ShapeDtypeStruct((B, H, S, D), jnp.bfloat16, sharding=one_chip)
    compiled = _compiled_flash_grad(x, x, x)
    text = compiled.as_text()
    assert text.count(KERNEL) == 2
    for scores in (f"[{B},{H},{block},{S}]", f"[{B},{H},{S},{S}]"):
        assert scores not in text
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries < 0.2e9 < B * H * block * S * 4


@pytest.mark.parametrize("seq, dtype", [(32768, jnp.bfloat16),
                                        (200, jnp.bfloat16),
                                        (197, jnp.float32)],
                         ids=["seq32768", "bucket200_not_pow2", "vit197_f32"])
def test_flash_backward_other_lengths(one_chip, seq, dtype):
    """The backward holds dq's float32 accumulator for the whole query
    length in VMEM (16.8 MB at 32,768 x 128, beside the output block twice:
    over the compiler's default limit, which the call raises by what it
    holds); and lengths that are one block, no multiple of the tile."""
    x = jax.ShapeDtypeStruct((1, 2, seq, 128), dtype, sharding=one_chip)
    assert _compiled_flash_grad(x, x, x).as_text().count(KERNEL) == 2


def _paged_call(one_chip, B, H, Hkv, pool_pages, table_pages, q_dtype,
                writes, sm_scale=None):
    """The paged kernel compiled alone: pages of 64 tokens, heads of 128,
    bfloat16 pools; with the step's rows handed in (`writes`: the pools
    donated, as the engine donates them) or read-only."""
    S = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    pool = S((pool_pages, Hkv, 64, 128), jnp.bfloat16)
    new = S((B, Hkv, 128), q_dtype)

    def call(q, k_pool, v_pool, tables, lengths, *rows):
        return paged_attention.paged_decode_attention_batch(
            q, k_pool, v_pool, tables, lengths, sm_scale=sm_scale,
            **dict(zip(("k_new", "v_new"), rows)))

    return jax.jit(call, donate_argnums=(1, 2) if writes else ()).lower(
        S((B, H, 128), q_dtype), pool, pool, S((B, table_pages), jnp.int32),
        S((B,), jnp.int32), *((new, new) if writes else ())).compile()


@pytest.mark.parametrize("B, pool_pages", [(32, 385), (4, 193)],
                         ids=["chat_open_b32", "docs_closed_b4"])
def test_paged_decode_batch(one_chip, B, pool_pages):
    """The two serve cells' shapes (`benchmarks/configs/mistral-7b-v0.3-
    l16*.json`: 32/8 heads of 128, pages of 64, a table of ceil((2304 + 8)
    / 64) = 37 columns, the pool with its dummy page), as a decode step
    calls the kernel: with the step's rows, the pools aliased in place."""
    compiled = _paged_call(one_chip, B, 32, 8, pool_pages, 37, jnp.bfloat16,
                           writes=True)
    assert KERNEL in compiled.as_text()
    pool_bytes = pool_pages * 8 * 64 * 128 * 2
    assert compiled.memory_analysis().alias_size_in_bytes >= 2 * pool_bytes
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


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
    compiled = _compiled_decode_chunk(eng, params, one_chip)
    assert KERNEL in compiled.as_text()
    assert _peak_bytes(compiled) < HBM_BYTES


# `benchmarks/configs/mistral-7b-v0.3-l16.json`: the engine of
# `mistral7b-serve-chat-open`.
CHAT_OPEN_ENGINE = dict(max_batch=32, max_len=2304, page_size=64,
                        decode_chunk=8, kv_pool_tokens=24576)


def test_decode_chunk_leaves_the_pools_where_they_lie(one_chip):
    """The decode chunk at chat-open's shapes (published widths, 385
    pages of 8 x 64 x 128 a pool: 50 MB), cut to 2 layers: no pool is
    copied to another layout or moved to another memory space, in the
    loop or around it.  The kernel writes the step's token itself, in
    place; a one-token scatter outside it made the compiler carry every
    pool token-major through the loop: at 2 layers 4 layout copies a
    step and 8 copies + 4 pools prefetched a chunk, at 16 layers 32
    copies + 31 pools moved a step and 64 copies + 40 pools moved a
    chunk (the parent of PR 29, this helper).  At the smoke's 34-page
    pool the compiler prefetches whole pools whatever form the write
    has, so that size tells nothing."""
    from ray_tpu.serve.llm import LLMEngine

    cfg = LlamaConfig(vocab_size=32768, d_model=4096, n_layers=2,
                      n_heads=32, n_kv_heads=8, d_ff=14336,
                      rope_theta=1e6)
    params = jax.eval_shape(
        lambda: LlamaModel(cfg).init(jax.random.PRNGKey(0),
                                     jnp.zeros((1, 8), jnp.int32)))
    eng = LLMEngine(cfg, params, **CHAT_OPEN_ENGINE)
    try:
        text = _compiled_decode_chunk(eng, params, one_chip).as_text()
        assert text.count(KERNEL) == cfg.n_layers
        moves = chip_smoke.state_moves(text, eng._pools)
        assert moves == {"loop": _NOTHING, "outside": _NOTHING}
    finally:
        eng.shutdown()


def test_state_moves_counts_what_a_compiled_program_holds():
    """The counter on a text with one of each: a layout copy in a loop's
    body, half a leaf prefetched in slices there, a whole leaf moved
    outside, and copies of other shapes, which do not count."""
    pool = "bf16[6,8,64,128]"
    text = f"""
%fused (p: {pool}) -> {pool} {{
  ROOT %copy.9 = {pool}{{3,2,1,0}} copy(%p)
}}

%body (arg: ({pool})) -> ({pool}) {{
  %copy.1 = {pool}{{3,1,2,0:T(8,128)(2,1)}} copy(%x)
  %copy.2 = bf16[32,128]{{1,0}} copy(%y)
  %slice-start.1 = (({pool}{{3,2,1,0}}), bf16[3,8,64,128]{{3,2,1,0:S(1)}}, s32[]) slice-start(%x), slice={{[0:3], [0:8], [0:64], [0:128]}}
  %f = {pool} fusion(%x), kind=kLoop, calls=%fused
}}

ENTRY %main (a: {pool}) -> {pool} {{
  %w = ({pool}) while(%t), condition=%cond, body=%body
  %copy-start.1 = ({pool}{{3,2,1,0:S(1)}}, {pool}{{3,2,1,0}}, u32[]) copy-start(%a)
  ROOT %copy.3 = f32[6,8,64,128]{{3,2,1,0}} copy(%b)
}}
"""
    state = [jax.ShapeDtypeStruct((6, 8, 64, 128), jnp.bfloat16)]
    assert chip_smoke.state_moves(text, state) == {
        "loop": {"copy": 2, "copy-start": 0, "slice-start": 1,
                 "moved": 0.5},
        "outside": {"copy": 0, "copy-start": 1, "slice-start": 0,
                    "moved": 1}}


def _compiled_prefill(eng, params, one_chip, W, bucket):
    """The engine's prefill of W rows of a bucket (`prefill_one` for a
    row alone, as the engine chooses), lowered and compiled for the chip."""
    program = eng._prefill_one if W == 1 else eng._prefill_many
    lowered = program.lower(
        params,
        jax.ShapeDtypeStruct((W, bucket), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((W,), jnp.int32, sharding=one_chip))
    return lowered, lowered.compile()


def test_engine_batched_prefill(engine_programs, one_chip):
    """The whole (W, bucket) program of the smoke's engine compiles and
    fits beside the weights; since PR 39 the prompt attends over itself
    through the flash forward kernel, once a layer."""
    cfg, eng, params = engine_programs
    W = eng._batch_prefill_width
    bucket = max(eng._bucket(chip_smoke.PROMPT_LENGTHS[-1]), eng.page_size)
    _, compiled = _compiled_prefill(eng, params, one_chip, W, bucket)
    assert compiled.as_text().count(KERNEL) == cfg.n_layers
    assert _peak_bytes(compiled) < HBM_BYTES


# `benchmarks/configs/mistral-7b-v0.3-l16-b4.json`: the engine of
# `mistral7b-serve-docs-closed`; the model of both dense serve cells as the
# harness builds it (`families/dense_decoder.program_config`: "reference"
# is what the cache-less path would run, a prefill does not ask it).
DOCS_CLOSED_ENGINE = dict(max_batch=4, max_len=2304, page_size=64,
                          decode_chunk=8, kv_pool_tokens=12288)
MISTRAL_L16 = LlamaConfig(vocab_size=32768, d_model=4096, n_layers=16,
                          n_heads=32, n_kv_heads=8, d_ff=14336,
                          rope_theta=1e6, attention="reference", remat=False)


@pytest.fixture(scope="module")
def mistral_params(one_chip):
    return _on(one_chip, jax.eval_shape(
        lambda: LlamaModel(MISTRAL_L16).init(jax.random.PRNGKey(0),
                                             jnp.zeros((1, 8), jnp.int32))))


@pytest.mark.parametrize("engine, W, bucket", [
    (CHAT_OPEN_ENGINE, 8, 1024), (CHAT_OPEN_ENGINE, 1, 64),
    (DOCS_CLOSED_ENGINE, 4, 2048), (DOCS_CLOSED_ENGINE, 1, 2304)],
    ids=["chat-8x1024", "chat-1x64", "docs-4x2048", "docs-1x2304"])
def test_dense_prefill_is_the_prompt_over_itself(one_chip, mistral_params,
                                                 engine, W, bucket):
    """The two dense cells' prefill programs at their real widths and
    depth: the batched program at its largest bucket, the single one at
    the smallest and at `max_len` itself (2304 = 9 x 256: no power of
    two, so the kernel's blocks are fitted). Each holds the flash forward
    kernel once a layer, no float32 scores of bucket x `max_len` a head
    (nor of bucket x bucket), no cache of `max_len` where the bucket is
    shorter, and returns K/V as long as the bucket."""
    from ray_tpu.serve.llm import LLMEngine

    cfg, max_len = MISTRAL_L16, engine["max_len"]
    eng = LLMEngine(cfg, mistral_params, **engine)
    try:
        lowered, compiled = _compiled_prefill(eng, mistral_params, one_chip,
                                              W, bucket)
    finally:
        eng.shutdown()
    logits, fresh = lowered.out_info
    assert logits.shape == (W, cfg.vocab_size)
    assert {x.shape for x in jax.tree_util.tree_leaves(fresh)} == \
        {(W, cfg.n_kv_heads, bucket, cfg.head_dim)}
    assert len(fresh) == cfg.n_layers
    text = compiled.as_text()
    assert text.count(KERNEL) == cfg.n_layers
    # (rope's float32 halves are (W, heads, bucket, 64): 64 keys tell nothing)
    keys = "|".join(str(n) for n in {max_len, bucket} - {cfg.head_dim // 2})
    assert not re.search(rf"f32\[\d+,\d+,{bucket},({keys})\]", text)
    if bucket < max_len:
        assert f",{max_len},{cfg.head_dim}]" not in text
    assert _peak_bytes(compiled) < HBM_BYTES


def test_docs_closed_prefill_needs_less_than_over_the_dense_cache(
        one_chip, mistral_params, capsys):
    """4 x 2048 tokens, docs-closed's largest prefill, needed 10.83 GB
    (weights, temporaries and outputs) while it attended over a float32
    (2048, 2304) block a head and returned caches of `max_len`; 8 x 2048,
    what `max_batch` 8 would compile, needed 14.13 GB beside no pool at
    all, which is why the cell has 4 slots. Printed: what both need now."""
    from ray_tpu.serve.llm import LLMEngine

    peaks = {}
    for slots in (4, 8):
        eng = LLMEngine(MISTRAL_L16, mistral_params,
                        **{**DOCS_CLOSED_ENGINE, "max_batch": slots})
        try:
            peaks[slots] = _peak_bytes(_compiled_prefill(
                eng, mistral_params, one_chip, slots, 2048)[1])
        finally:
            eng.shutdown()
    with capsys.disabled():
        print("\nprefill_many peak bytes (weights 7.52 GB among them): "
              f"4 x 2048 {peaks[4]:,}, 8 x 2048 {peaks[8]:,}")
    assert peaks[4] < 10.83e9
    assert peaks[8] < 14.13e9


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


# `internlm2-train-packed2k` (`benchmarks/configs/internlm2-1.8b.json`:
# InternLM2-1.8B whole, 4 rows of 2,048 tokens, remat "full").  Its ceiling
# is the peak the cell held on the chip from PR 23 to PR 30: what "full"
# keeps of a layer has to stay under it (PR 34: 12.63 GB with the kernel's
# results, q, k, v and the attention block's output kept; 9.53 GB with
# nothing kept).
CELL_TRAIN = LlamaConfig(vocab_size=92544, d_model=2048, n_layers=24,
                         n_heads=16, n_kv_heads=8, d_ff=8192,
                         max_seq_len=32768, rope_theta=1e6,
                         **chip_smoke.TRAIN_OVERRIDES)


@_WHOLE_STEP_LIMIT
@pytest.mark.parametrize("cfg, rows, ceiling", [
    pytest.param(chip_smoke.smoke_config(LLAMA3_8B, chip_smoke.TRAIN_LAYERS,
                                         **chip_smoke.TRAIN_OVERRIDES),
                 chip_smoke.TRAIN_BATCH, HBM_BYTES,
                 id="smoke_llama3_8b_12_layers"),
    pytest.param(CELL_TRAIN, 4, 12.85e9,
                 id="cell_internlm2_train_packed2k")])
def test_train_step_fits_one_chip(topo, cfg, rows, ceiling):
    from ray_tpu.parallel import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(fsdp=1), devices=topo.devices[:1])
    step, state, batch = _abstract_train(cfg, mesh, rows,
                                         chip_smoke.TRAIN_SEQ)
    compiled = step.lower(state, batch).compile()
    # A layer's forward kernel once and its backward kernel once: remat
    # "full" keeps the forward kernel's results, so the layer's re-run
    # forward holds no kernel (three a layer before PR 34).
    assert compiled.as_text().count(KERNEL) == 2 * cfg.n_layers
    assert _peak_bytes(compiled) <= ceiling


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
    # Under the mesh the kernels run inside `jax.shard_map`
    # (`llama._flash_on_mesh`); the names `_fa_fwd` gives their results
    # inside the mapped function reach the checkpoint policy all the same:
    # two kernels a layer here too, not three.
    assert compiled.as_text().count(KERNEL) == 2 * cfg.n_layers
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
    float32 queries and rows) and the flash kernel over a 16,384-token
    prompt."""
    S = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    for writes in (True, False):    # the full layer's call, a cross layer's
        compiled = _paged_call(one_chip, 32, 40, 10, 4737, 274, jnp.float32,
                               writes, sm_scale=0.125)
        assert KERNEL in compiled.as_text()
    qkv = S((1, 40, 16384, 128), jnp.bfloat16)
    compiled = jax.jit(lambda q, k, v: attention.flash_attention(
        q, k, v, 0.125, True)).lower(qkv, qkv, qkv).compile()
    assert KERNEL in compiled.as_text()


def _sambay_engine(cfg):
    from ray_tpu.models.sambay import SambaYModel
    from ray_tpu.serve.llm import LLMEngine

    params = jax.eval_shape(
        lambda: SambaYModel(cfg).init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 8), jnp.int32)))
    return LLMEngine(cfg, params, **SAMBAY_ENGINE), params


def _assert_the_pool_stays(text, eng):
    """No copy or move of the one shared pool (775 MB a side), in the
    loop or around it: the full layer's kernel call writes the token in
    place and the cross layers read what it returned.  (The rings are
    still written by a scatter outside any kernel, and still copied: one
    layout copy a ring and step, two a ring and chunk.  Held token-major,
    (B, window, Hkv/2, 2 Dh), the write needs none, but the attention's
    dot then takes its operand through a transposing copy of the same
    size, every step: PERF.md section 6, PR 29.)"""
    moves = chip_smoke.state_moves(text, eng._pools["pool"])
    assert moves == {"loop": _NOTHING, "outside": _NOTHING}


def test_sambay_decode_chunk_leaves_the_pool_where_it_lies(one_chip):
    """Published widths and the cell's engine, cut to 8 layers: every
    kind of layer occurs (three Mamba, two window, the full one, a GMU
    and a cross layer that reads the pool the full layer's call
    returned)."""
    import dataclasses

    from ray_tpu.models.sambay import PHI4_MINI_FLASH

    cfg = dataclasses.replace(PHI4_MINI_FLASH, n_layers=8)
    assert {cfg.kind(i) for i in range(8)} == {
        "mamba", "window", "full", "gmu", "cross"}
    eng, params = _sambay_engine(cfg)
    try:
        text = _compiled_decode_chunk(eng, params, one_chip).as_text()
        assert text.count(KERNEL) == 2
        _assert_the_pool_stays(text, eng)
    finally:
        eng.shutdown()


@pytest.mark.slow      # 45 s of a many-threaded compile: by hand, not in tier-1
@_WHOLE_STEP_LIMIT
def test_sambay_engine_programs_fit_the_chip(one_chip):
    """The cell's decode chunk (eight paged calls a step, rings and
    recurrent state carried through the scan, a count of steps a slot)
    and its largest prefill (one row of 16,384 tokens: the scan, eight
    windowed layers in blocks, flash over the full layer) at published
    widths, each beside the weights and the engine's whole state."""
    from ray_tpu.models.sambay import PHI4_MINI_FLASH

    eng, params = _sambay_engine(PHI4_MINI_FLASH)
    try:
        B = eng.max_batch
        S = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=one_chip)
        state_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                          for x in jax.tree_util.tree_leaves(eng._pools))
        decode = _compiled_decode_chunk(eng, params, one_chip)
        assert decode.as_text().count(KERNEL) == 8
        _assert_the_pool_stays(decode.as_text(), eng)
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


# ---- the Granite hybrid family at the sizes of `granite4h-serve-rows-closed` --


def _granite_cell():
    import json

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "granite-4.0-h-micro.json")) as f:
        return json.load(f)


def test_granite_kernels_at_the_cells_shapes(one_chip):
    """Heads of 64 run as halves of heads of 128: 32 zero-padded query
    heads over 4 paired KV heads, scale 1/64.  The paged kernel over one
    attention layer's pool (max_batch x 25 pages of 64 and the dummy, a
    table of ceil((1600 + 8) / 64) = 26 columns, float32 queries, the
    step's rows written in place) and the flash kernel over the largest
    prefill, 8 rows of 1,024 tokens."""
    B = _granite_cell()["serve"]["engine"]["max_batch"]
    compiled = _paged_call(one_chip, B, 32, 4, B * 25 + 1, 26, jnp.float32,
                           writes=True, sm_scale=1 / 64)
    assert KERNEL in compiled.as_text()
    qkv = jax.ShapeDtypeStruct((8, 32, 1024, 128), jnp.bfloat16,
                               sharding=one_chip)
    compiled = jax.jit(lambda q, k, v: attention.flash_attention(
        q, k, v, 1 / 64, True)).lower(qkv, qkv, qkv).compile()
    assert KERNEL in compiled.as_text()


@pytest.mark.time_limit(900)   # two programs of 40 layers: 110 s alone here
def test_granite_engine_programs_fit_the_chip_and_leave_the_state(one_chip):
    """The cell's engine at published widths and whole depth, built from
    the configuration file: the decode chunk (36 state steps and 4 paged
    calls a step, 4.9 GB of recurrent state carried through the scan, a
    count of steps a slot) and the largest prefill (8 rows of 1,024
    tokens through the chunked scan and flash) hold the bytes the file's
    `memory` records; no buffer of the state's shape is copied to another
    layout, in the loop or around it, and no S (134 MB a layer) or pool is
    moved at all: each state step is one fusion that reads S once and
    writes it once, in place (the conv windows, 1.7 MB a layer, are
    prefetched to fast memory like weights)."""
    from benchmarks.families import granite_hybrid as family
    from ray_tpu.models.granite_hybrid import GraniteHybridModel
    from ray_tpu.serve.llm import LLMEngine

    conf = _granite_cell()
    cfg = family.program_config(family.sizes(conf))
    params = jax.eval_shape(
        lambda: GraniteHybridModel(cfg).init(jax.random.PRNGKey(0),
                                             jnp.zeros((1, 8), jnp.int32)))
    eng = LLMEngine(cfg, params, **conf["serve"]["engine"])
    try:
        B = eng.max_batch
        recorded = conf["memory"]["tried"][str(B)]
        S = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=one_chip)
        gb = lambda tree: sum(  # noqa: E731
            int(np.prod(x.shape)) * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(tree)) / 1e9
        assert gb(params) == pytest.approx(conf["memory"]["weights_gb"],
                                           abs=1e-3)
        assert gb(eng._pools) == pytest.approx(recorded["state_gb"]["all"],
                                               abs=1e-3)
        assert eng.family.state_bytes_per_slot == \
            conf["memory"]["recurrent_bytes_per_sequence"]
        decode = _compiled_decode_chunk(eng, params, one_chip)
        text = decode.as_text()
        assert text.count(KERNEL) == 4
        conv, state = zip(*eng._pools["ssm"])
        nothing = {"loop": _NOTHING, "outside": _NOTHING}
        assert chip_smoke.state_moves(text, eng._pools["pools"]) == nothing
        assert chip_smoke.state_moves(text, state) == nothing
        moves = chip_smoke.state_moves(text, conv)
        assert moves["loop"]["copy"] == moves["outside"]["copy"] == 0
        assert _peak_bytes(decode) / 1e9 == pytest.approx(
            recorded["decode_chunk_paged_gb"]["peak_with_weights_and_state"],
            abs=0.05)
        assert eng.family.prefill_width(1024, B) == 8
        prefill = eng._prefill_many.lower(
            _on(one_chip, params), S((8, 1024), jnp.int32),
            S((8,), jnp.int32)).compile()
        assert KERNEL in prefill.as_text()
        # (the state is not an argument of the prefill: it is resident)
        resident = _peak_bytes(prefill) / 1e9 + gb(eng._pools)
        assert resident == pytest.approx(
            recorded["prefill_many_8x1024_gb"]["peak_with_state_resident"],
            abs=0.05)
        assert resident * 1e9 < 15.75e9 < HBM_BYTES
    finally:
        eng.shutdown()


# ---- the LFM2-MoE family at the sizes of `lfm2moe-serve-agents-closed` -----


def _tiles_seen(monkeypatch) -> list:
    """(rows, groups, tiles) of every call of the grouped product's kernel
    (`ops/grouped_matmul._grouped_call`) made while a program is traced,
    in order: what `_tiles` chose, and that the rows are float32 rows."""
    from ray_tpu.ops import grouped_matmul

    seen, real = [], grouped_matmul._grouped_call

    def call(x, w, sizes, *, tiles, **kw):
        assert x.dtype == jnp.float32 and w.dtype == jnp.bfloat16
        seen.append((x.shape[0], w.shape[0], tiles))
        return real(x, w, sizes, tiles=tiles, **kw)

    monkeypatch.setattr(grouped_matmul, "_grouped_call", call)
    return seen


@pytest.mark.parametrize("tokens, top_k, d, f", [
    (64, 6, 2048, 1408), (8192, 6, 2048, 1408), (1024, 4, 2048, 1536)],
    ids=["kimi_decode_64", "kimi_prompt_8192", "lfm2_prompt_1024"])
def test_routed_layer_holds_no_doubled_rows(one_chip, monkeypatch, tokens,
                                            top_k, d, f):
    """`expert_ffn` at a decode step's and at a prompt's shapes, lowered
    and compiled for the chip: two Pallas calls, and no array of 2 x pairs
    rows anywhere around them (until PR 47 each product's rows were laid
    out as (pairs, 2, k) and copied to (2 pairs, k), its result back): the
    kernel makes the two terms itself."""
    from ray_tpu.models import lfm2_moe
    from ray_tpu.ops import grouped_matmul

    monkeypatch.setattr(grouped_matmul, "_interpret_mode", lambda: False)
    S = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    lowered = jax.jit(lfm2_moe.expert_ffn).lower(
        S((tokens, d), jnp.float32), S((tokens, top_k), jnp.int32),
        S((tokens, top_k), jnp.float32), S((64, d, 2 * f), jnp.bfloat16),
        S((64, f, d), jnp.bfloat16))
    compiled = lowered.compile().as_text()
    assert compiled.count(KERNEL) == 2
    pairs = tokens * top_k
    assert re.search(rf"tensor<{pairs}x{d}xf32>", lowered.as_text())
    assert not re.search(rf"tensor<{2 * pairs}x", lowered.as_text())
    assert re.search(rf"f32\[{pairs},{d}\]", compiled)
    assert not re.search(rf"\[{2 * pairs},", compiled)


@pytest.mark.time_limit(600)   # two programs of 9 layers: 60 s alone here
def test_lfm2_moe_engine_programs_fit_the_chip(one_chip, monkeypatch):
    """The cell's engine at published widths, built from the configuration
    file: the decode chunk (16 grouped products and 2 paged calls a step:
    18 Pallas calls, the experts' 9.7 GB resident and not copied) and the
    largest batched prefill (2 rows of 4,096 tokens: 65,536 (row, expert)
    pair rows of two terms through the grouped products) hold the bytes
    the file's `memory` records, beside 10.36 GB of weights."""
    import json

    from benchmarks.families import lfm2_moe as family
    from ray_tpu.models import lfm2_moe
    from ray_tpu.ops import grouped_matmul
    from ray_tpu.serve.llm import LLMEngine

    monkeypatch.setattr(grouped_matmul, "_interpret_mode", lambda: False)
    seen = _tiles_seen(monkeypatch)
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "lfm2-24b-a2b-l9.json")) as f:
        conf = json.load(f)
    cfg = family.program_config(family.sizes(conf))
    params = jax.eval_shape(
        lambda: lfm2_moe.Lfm2MoeModel(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    eng = LLMEngine(cfg, params, **conf["serve"]["engine"])
    try:
        recorded = conf["memory"]
        S = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=one_chip)
        gb = lambda tree: sum(  # noqa: E731
            int(np.prod(x.shape)) * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(tree)) / 1e9
        assert gb(params) == pytest.approx(recorded["weights_gb"], abs=1e-3)
        assert gb(eng._pools) == pytest.approx(recorded["state_gb"]["all"],
                                               abs=1e-3)
        assert eng.family.state_bytes_per_slot == \
            recorded["conv_window_bytes_per_sequence"]
        del seen[:]         # (the engine traced its programs' shapes)
        decode = _compiled_decode_chunk(eng, params, one_chip)
        assert decode.as_text().count(KERNEL) == 18
        # 16 slots x 4 experts: 1 float32 row a group, one 64-row tile,
        # and an expert's whole matrix a slab
        assert seen == 8 * [(64, 64, (64, 2048, 3072)),
                            (64, 64, (64, 1536, 2048))]
        peak = recorded["decode_chunk_paged_gb"]["peak_with_weights_and_state"]
        assert peak - 0.05 < _peak_bytes(decode) / 1e9 < peak + 0.005
        del seen[:]
        assert eng.family.prefill_width(4096, eng.max_batch) == 2
        prefill = eng._prefill_many.lower(
            _on(one_chip, params), S((2, 4096), jnp.int32),
            S((2,), jnp.int32)).compile()
        assert prefill.as_text().count(KERNEL) >= 18
        # 8,192 tokens, 512 float32 rows a group: the same tiles
        assert set(seen) == {(32768, 64, (64, 2048, 3072)),
                             (32768, 64, (64, 1536, 2048))}
        resident = _peak_bytes(prefill) / 1e9 + gb(eng._pools)
        # (the file records PR 42's 13.12 GB, over doubled rows)
        assert resident == pytest.approx(13.12, abs=0.05) and resident <= \
            recorded["prefill_many_2x4096_gb"]["peak_with_state_resident"]
        assert resident * 1e9 < 15.75e9 < HBM_BYTES
    finally:
        eng.shutdown()


# ---- the latent-attention family at the sizes of `kimivl-serve-pages-closed`


@pytest.mark.parametrize("control", [
    None, "kernel_query_and_weights_in_one_bf16_term"])
def test_latent_kernel_at_the_cells_shapes(one_chip, control):
    """The latent-page kernel at the cell's shapes (64 rows, a table of
    146 pages, a pool of 5,633 pages of 64 rows of 640): Mosaic takes it,
    and the pool is aliased from input to output.  Also with the control
    that `benchmarks/tools/mla_moe_faults.py` plants in it on the chip
    (the query and the softmax's weights in one bfloat16 term)."""
    from benchmarks.tools.mla_moe_faults import planted

    S = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    pool = S((5633, 64, 640), jnp.bfloat16)
    with planted(control):
        compiled = jax.jit(
            functools.partial(paged_attention.paged_latent_attention_batch,
                              d_value=512, sm_scale=192 ** -0.5),
            donate_argnums=(1,)).lower(
                S((64, 16, 640), jnp.float32), pool, S((64, 146), jnp.int32),
                S((64,), jnp.int32), S((64, 640), jnp.bfloat16)).compile()
    assert compiled.as_text().count(KERNEL) == 1
    m = compiled.memory_analysis()
    pool_bytes = 5633 * 64 * 640 * 2
    assert m.alias_size_in_bytes >= pool_bytes
    assert m.temp_size_in_bytes < pool_bytes // 10


@pytest.mark.time_limit(600)   # two programs of 7 layers: 75 s alone here
def test_mla_moe_engine_programs_fit_the_chip(one_chip, monkeypatch):
    """The cell's engine at published widths, built from the configuration
    file: the decode chunk (7 latent calls and 12 grouped products a step:
    19 Pallas calls; no pool copied or moved) and the largest prefill (one
    row of 8,192 tokens) hold the bytes the file's `memory` records,
    beside 8.53 GB of weights and 3.23 GB of latent pages."""
    import json

    from benchmarks.families import mla_moe as family
    from ray_tpu.models import mla_moe
    from ray_tpu.ops import grouped_matmul
    from ray_tpu.serve.llm import LLMEngine

    monkeypatch.setattr(grouped_matmul, "_interpret_mode", lambda: False)
    seen = _tiles_seen(monkeypatch)
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "kimi-vl-a3b-l7.json")) as f:
        conf = json.load(f)
    cfg = family.program_config(family.sizes(conf))
    params = jax.eval_shape(
        lambda: mla_moe.MlaMoeModel(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    engine = conf["serve"]["engine"]
    # (the engine's own pools are made on this machine's CPU: a page a
    # slot here, the cell's 5,633 pages as shapes below)
    eng = LLMEngine(cfg, params, **dict(engine, kv_pool_tokens=64 * 64))
    try:
        recorded = conf["memory"]
        S = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=one_chip)
        gb = lambda tree: sum(  # noqa: E731
            int(np.prod(x.shape)) * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(tree)) / 1e9
        pools = jax.eval_shape(lambda: eng.family.init_state(
            engine["max_batch"],
            engine["kv_pool_tokens"] // engine["page_size"] + 1,
            engine["page_size"]))
        assert gb(params) == pytest.approx(recorded["weights_gb"], abs=1e-3)
        assert gb(pools) == pytest.approx(recorded["state_gb"]["all"],
                                          abs=1e-3)
        assert eng.family.state_bytes_per_slot == 0
        B = eng.max_batch
        del seen[:]         # (the engine traced its programs' shapes)
        decode = eng._decode_chunk_paged.lower(
            _on(one_chip, params), S((B,), jnp.int32), S((B,), jnp.int32),
            _on(one_chip, pools), S(eng._tables.shape, jnp.int32),
            S((B,), jnp.int32), S((B,), jnp.float32), S((B,), jnp.int32),
            S((B,), jnp.float32), S((2,), jnp.uint32),
            S((), jnp.int32)).compile()
        text = decode.as_text()
        assert text.count(KERNEL) == \
            recorded["decode_chunk_paged_gb"]["pallas_calls"] == 19
        assert chip_smoke.state_moves(text, pools) == {
            "loop": _NOTHING, "outside": _NOTHING}
        # 64 slots x 6 experts: 6 float32 rows a group, 64-row tiles, and
        # an expert's whole matrix a slab
        assert seen == 6 * [(384, 64, (64, 2048, 2816)),
                            (384, 64, (64, 1408, 2048))]
        peak = recorded["decode_chunk_paged_gb"]["peak_with_weights_and_state"]
        assert peak - 0.05 < _peak_bytes(decode) / 1e9 < peak + 0.005
        del seen[:]
        assert eng.family.prefill_width(8192, B) == 1
        prefill = eng._prefill_one.lower(
            _on(one_chip, params), S((1, 8192), jnp.int32),
            S((1,), jnp.int32)).compile()
        assert prefill.as_text().count(KERNEL) == 19
        # 8,192 tokens, 768 float32 rows a group: the same tiles
        assert set(seen) == {(49152, 64, (64, 2048, 2816)),
                             (49152, 64, (64, 1408, 2048))}
        resident = _peak_bytes(prefill) / 1e9 + gb(pools)
        # (the file records PR 45's 14.23 GB, over doubled rows)
        assert resident == pytest.approx(14.15, abs=0.05) and resident < \
            recorded["prefill_one_8192_gb"]["peak_with_state_resident"]
        assert resident * 1e9 < 15.75e9 < HBM_BYTES
    finally:
        eng.shutdown()
