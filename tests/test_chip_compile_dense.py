"""The dense engine's programs compiled for a described v5e: the smoke's
engine (`chip_smoke.py`) and the two dense serve cells'
(`tests/chip_compile.py` says how)."""

import re

import jax
import pytest

import chip_smoke
from ray_tpu.models.llama import (LLAMA3_8B, LlamaConfig,
                                  LlamaModel)
from tests.chip_compile import (HBM_BYTES, KERNEL, NO_MOVES,  # noqa: F401
                                _compile_for_the_chip, abstract_params,
                                compiled_decode_chunk, compiled_prefill, on,
                                one_chip, peak_bytes, topo)


@pytest.fixture(scope="module")
def engine_programs(one_chip):
    """The serve phase's engine at the smoke's widths and depth, built
    around parameter SHAPES (no array of that size exists here)."""
    from ray_tpu.serve.llm import LLMEngine

    cfg = chip_smoke.smoke_config(LLAMA3_8B, chip_smoke.SERVE_LAYERS)
    params = abstract_params(LlamaModel(cfg))
    eng = LLMEngine(cfg, params, **chip_smoke.ENGINE_KWARGS)
    yield cfg, eng, on(one_chip, params)
    eng.shutdown()


def test_engine_decode_step(engine_programs, one_chip):
    cfg, eng, params = engine_programs
    compiled = compiled_decode_chunk(eng, params, one_chip)
    assert KERNEL in compiled.as_text()
    assert peak_bytes(compiled) < HBM_BYTES


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
    params = abstract_params(LlamaModel(cfg))
    eng = LLMEngine(cfg, params, **CHAT_OPEN_ENGINE)
    try:
        text = compiled_decode_chunk(eng, params, one_chip).as_text()
        assert text.count(KERNEL) == cfg.n_layers
        assert chip_smoke.state_moves(text, eng._pools) == NO_MOVES
    finally:
        eng.shutdown()


def test_engine_batched_prefill(engine_programs, one_chip):
    """The whole (W, bucket) program of the smoke's engine compiles and
    fits beside the weights; since PR 39 the prompt attends over itself
    through the flash forward kernel, once a layer."""
    cfg, eng, params = engine_programs
    W = eng._batch_prefill_width
    bucket = max(eng._bucket(chip_smoke.PROMPT_LENGTHS[-1]), eng.page_size)
    _, compiled = compiled_prefill(eng, params, one_chip, W, bucket)
    assert compiled.as_text().count(KERNEL) == cfg.n_layers
    assert peak_bytes(compiled) < HBM_BYTES


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
def dense_prefill(one_chip):
    """-> prefill(engine, W, bucket): the lowered and the compiled prefill of
    W rows of a bucket in an engine of these keywords, at Mistral-7B's widths
    and 16 layers.  A program is compiled once for every test of this file
    that reads it (docs-closed's 4 x 2048 is read by two)."""
    from ray_tpu.serve.llm import LLMEngine

    params = on(one_chip, abstract_params(LlamaModel(MISTRAL_L16)))
    programs = {}

    def prefill(engine, W, bucket):
        key = (tuple(sorted(engine.items())), W, bucket)
        if key not in programs:
            eng = LLMEngine(MISTRAL_L16, params, **engine)
            try:
                programs[key] = compiled_prefill(eng, params, one_chip, W,
                                                 bucket)
            finally:
                eng.shutdown()
        return programs[key]

    return prefill


@pytest.mark.parametrize("engine, W, bucket", [
    (CHAT_OPEN_ENGINE, 8, 1024), (CHAT_OPEN_ENGINE, 1, 64),
    (DOCS_CLOSED_ENGINE, 4, 2048), (DOCS_CLOSED_ENGINE, 1, 2304)],
    ids=["chat-8x1024", "chat-1x64", "docs-4x2048", "docs-1x2304"])
def test_dense_prefill_is_the_prompt_over_itself(dense_prefill, engine, W,
                                                 bucket):
    """The two dense cells' prefill programs at their real widths and
    depth: the batched program at its largest bucket, the single one at
    the smallest and at `max_len` itself (2304 = 9 x 256: no power of
    two, so the kernel's blocks are fitted). Each holds the flash forward
    kernel once a layer, no float32 scores of bucket x `max_len` a head
    (nor of bucket x bucket), no cache of `max_len` where the bucket is
    shorter, and returns K/V as long as the bucket."""
    cfg, max_len = MISTRAL_L16, engine["max_len"]
    lowered, compiled = dense_prefill(engine, W, bucket)
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
    assert peak_bytes(compiled) < HBM_BYTES


def test_docs_closed_prefill_needs_less_than_over_the_dense_cache(
        dense_prefill, capsys):
    """4 x 2048 tokens, docs-closed's largest prefill, needed 10.83 GB
    (weights, temporaries and outputs) while it attended over a float32
    (2048, 2304) block a head and returned caches of `max_len`; 8 x 2048,
    what `max_batch` 8 would compile, needed 14.13 GB beside no pool at
    all, which is why the cell has 4 slots. Printed: what both need now."""
    peaks = {slots: peak_bytes(dense_prefill(
        {**DOCS_CLOSED_ENGINE, "max_batch": slots}, slots, 2048)[1])
        for slots in (4, 8)}
    with capsys.disabled():
        print("\nprefill_many peak bytes (weights 7.52 GB among them): "
              f"4 x 2048 {peaks[4]:,}, 8 x 2048 {peaks[8]:,}")
    assert peaks[4] < 10.83e9
    assert peaks[8] < 14.13e9
