"""The SambaY family at the widths of `phi4flash-serve-reason-closed`,
compiled for a described v5e (`tests/chip_compile.py` says how)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from ray_tpu.ops import attention
from tests.chip_compile import (HBM_BYTES, KERNEL, NO_MOVES,  # noqa: F401
                                _compile_for_the_chip, abstract_params,
                                compiled_decode_chunk, compiled_prefill, gb,
                                one_chip, paged_call, peak_bytes, topo)

SAMBAY_ENGINE = dict(max_batch=32, max_len=17472, page_size=64,
                     decode_chunk=8, kv_pool_tokens=303104)


def test_sambay_kernels_at_the_cells_shapes(one_chip):
    """Heads of 64 run as pairs: 40 zero-padded query heads and 10 KV
    heads of 128, scale 1/8.  The paged kernel over the one shared pool
    (4,737 pages of 64, a table of ceil((17472 + 8) / 64) = 274 columns,
    float32 queries and rows) and the flash kernel over a 16,384-token
    prompt."""
    for writes in (True, False):    # the full layer's call, a cross layer's
        compiled = paged_call(one_chip, 32, 40, 10, 4737, 274, jnp.float32,
                              writes, sm_scale=0.125)
        assert KERNEL in compiled.as_text()
    qkv = jax.ShapeDtypeStruct((1, 40, 16384, 128), jnp.bfloat16,
                               sharding=one_chip)
    compiled = jax.jit(lambda q, k, v: attention.flash_attention(
        q, k, v, 0.125, True)).lower(qkv, qkv, qkv).compile()
    assert KERNEL in compiled.as_text()


def _sambay_engine(cfg):
    from ray_tpu.models.sambay import SambaYModel
    from ray_tpu.serve.llm import LLMEngine

    params = abstract_params(SambaYModel(cfg))
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
    assert chip_smoke.state_moves(text, eng._pools["pool"]) == NO_MOVES


def test_sambay_decode_chunk_leaves_the_pool_where_it_lies(one_chip):
    """Published widths and the cell's engine, cut to 8 layers: every
    kind of layer occurs (three Mamba, two window, the full one, a GMU
    and a cross layer that reads the pool the full layer's call
    returned)."""
    from ray_tpu.models.sambay import PHI4_MINI_FLASH

    cfg = dataclasses.replace(PHI4_MINI_FLASH, n_layers=8)
    assert {cfg.kind(i) for i in range(8)} == {
        "mamba", "window", "full", "gmu", "cross"}
    eng, params = _sambay_engine(cfg)
    try:
        text = compiled_decode_chunk(eng, params, one_chip).as_text()
        assert text.count(KERNEL) == 2
        _assert_the_pool_stays(text, eng)
    finally:
        eng.shutdown()


@pytest.mark.slow     # 45 s of a many-threaded compile: by hand, not in tier-1
@pytest.mark.time_limit(600)
def test_sambay_engine_programs_fit_the_chip(one_chip):
    """The cell's decode chunk (eight paged calls a step, rings and
    recurrent state carried through the scan, a count of steps a slot)
    and its largest prefill (one row of 16,384 tokens: the scan, eight
    windowed layers in blocks, flash over the full layer) at published
    widths, each beside the weights and the engine's whole state."""
    from ray_tpu.models.sambay import PHI4_MINI_FLASH

    eng, params = _sambay_engine(PHI4_MINI_FLASH)
    try:
        decode = compiled_decode_chunk(eng, params, one_chip)
        assert decode.as_text().count(KERNEL) == 8
        _assert_the_pool_stays(decode.as_text(), eng)
        assert peak_bytes(decode) < HBM_BYTES
        assert eng.family.prefill_width(16384, eng.max_batch) == 1
        _, prefill = compiled_prefill(eng, params, one_chip, 1, 16384)
        assert KERNEL in prefill.as_text()
        # (the state is not an argument of the prefill: it is resident)
        assert peak_bytes(prefill) + gb(eng._pools) * 1e9 < HBM_BYTES
    finally:
        eng.shutdown()

