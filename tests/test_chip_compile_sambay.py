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
    float32 queries and rows), and the flash kernel over 16,384 tokens as
    the WHOLE forward (`SambaYModel.__call__`, every layer at every
    position) calls it for the full-attention layer.  No program of the
    cell's engine calls it since PR 51: a prefill attends ONE query a row
    over the full layer's K and V
    (`test_sambay_prefills_call_no_kernel_and_fit_beside_the_state`)."""
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


# What PR 50's programs (the flash kernel over the full layer, compiled here
# the same way) held with the engine's state resident: 13.99 GB at
# 1 x 16,384, 13.49 at 2 x 8,192.  With the full layer at one query a row
# (PR 51): 13.994 and 13.488, unchanged.  The limits leave the readings
# 0.06 GB.
SAMBAY_PREFILL_BYTES = {(1, 16384): 14.05e9, (2, 8192): 13.55e9}


@pytest.mark.time_limit(400)
def test_sambay_prefills_call_no_kernel_and_fit_beside_the_state(one_chip):
    """The cell's two largest prefill programs at published widths: layers
    0-16 over the whole bucket, the full layer but for its K and V and the
    cross-decoder at one token a row.  No Pallas call is left in them (the
    flash kernel's was the only one), and beside the engine's resident
    state they hold what PR 50's did (the scan and the window layers'
    temporaries set the peak, not the full layer)."""
    from ray_tpu.models.sambay import PHI4_MINI_FLASH

    eng, params = _sambay_engine(PHI4_MINI_FLASH)
    try:
        held = {}
        for W, bucket in SAMBAY_PREFILL_BYTES:
            assert eng.family.prefill_width(bucket, eng.max_batch) == W
            lowered, prefill = compiled_prefill(eng, params, one_chip, W,
                                                bucket)
            assert KERNEL not in prefill.as_text()
            assert "flash" not in lowered.as_text()
            # (the state is not an argument of the prefill: it is resident)
            held[W, bucket] = peak_bytes(prefill) + gb(eng._pools) * 1e9
        assert all(held[k] < limit < HBM_BYTES
                   for k, limit in SAMBAY_PREFILL_BYTES.items()), held
    finally:
        eng.shutdown()


@pytest.mark.slow     # 45 s of a many-threaded compile: by hand, not in tier-1
@pytest.mark.time_limit(600)
def test_sambay_engine_programs_fit_the_chip(one_chip):
    """The cell's decode chunk (eight paged calls a step, rings and
    recurrent state carried through the scan, a count of steps a slot)
    at published widths beside the weights and the engine's whole state
    (its prefills:
    `test_sambay_prefills_call_no_kernel_and_fit_beside_the_state`)."""
    from ray_tpu.models.sambay import PHI4_MINI_FLASH

    eng, params = _sambay_engine(PHI4_MINI_FLASH)
    try:
        decode = compiled_decode_chunk(eng, params, one_chip)
        assert decode.as_text().count(KERNEL) == 8
        _assert_the_pool_stays(decode.as_text(), eng)
        assert peak_bytes(decode) < HBM_BYTES
    finally:
        eng.shutdown()

