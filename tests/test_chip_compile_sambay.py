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
                                compiled_decode_chunk, gb, on, one_chip,
                                paged_call, peak_bytes, shape_on, topo)

SAMBAY_ENGINE = dict(max_batch=32, max_len=17472, page_size=64,
                     decode_chunk=8, kv_pool_tokens=303104)


def test_sambay_kernels_at_the_cells_shapes(one_chip):
    """Heads of 64 run as pairs: 40 zero-padded query heads and 10 KV
    heads of 128, scale 1/8.  The paged kernel over the one shared pool
    (4,737 pages of 64, a table of ceil((17472 + 8) / 64) = 274 columns,
    float32 queries and rows), and the flash kernel over 16,384 tokens as
    the WHOLE forward (`SambaYModel.__call__`, every layer at every
    position) calls it for the full-attention layer.  No program of the
    cell's engine calls it since PR 51: a prompt's tail attends ONE query a
    row over the full layer's K and V
    (`test_a_sambay_prompts_programs_call_no_kernel_and_fit_beside_the_state`)."""
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


def _block_program(eng, params, one_chip, W):
    """The family's ONE program of layers 0-16 at a width, a block of
    positions after a state (donated), and shapes of its arguments."""
    fam, S = eng.family, shape_on(one_chip)
    state = jax.eval_shape(lambda: fam.model.fresh_state(W))
    return fam._block, (
        on(one_chip, params), S((W, fam.block), jnp.int32),
        S((), jnp.int32), S((W,), jnp.int32), on(one_chip, state))


def _tail_program(eng, params, one_chip, W, bucket):
    """... and the program a (width, bucket): the blocks' K and V laid end
    to end, the full layer for one query a row, the cross-decoder, the
    head."""
    fam, S = eng.family, shape_on(one_chip)
    state, kv = jax.eval_shape(lambda: fam._fresh(W))
    return fam._tail, (
        bucket, on(one_chip, params), on(one_chip, state),
        [on(one_chip, kv)] * (-(-bucket // fam.block)), S((W,), jnp.int32))


def _held_bytes(compiled, args, eng) -> float:
    """What a program holds at its peak with the engine's state and ALL of
    the weights resident (a program's own arguments leave out the layers it
    does not run): its temporaries and outputs, its arguments as handed in,
    the engine's state."""
    m = compiled.memory_analysis()
    return m.temp_size_in_bytes + m.output_size_in_bytes \
        - m.alias_size_in_bytes + (gb(args) + gb(eng._pools)) * 1e9


# What the parent's whole-bucket programs held with the weights and the
# engine's state resident (PR 51, compiled here the same way): 13.994 GB at
# 1 x 16,384, 13.488 at 2 x 8,192.  A prompt's programs now, accounted the
# same way (`_held_bytes`): the block at 1 x 512 reads 10.199 GB and at the
# widest group, 8 x 512, 11.514; the tails of the parent's two groups, with
# the rows' K and V as the blocks left them among their arguments, 10.226
# and 10.251.  Each limit leaves its reading 0.1 GB.
SAMBAY_PROMPT_BYTES = {("block", 1): 10.3e9, ("block", 8): 11.62e9,
                       ("tail", 1, 16384): 10.33e9,
                       ("tail", 2, 8192): 10.36e9}


@pytest.mark.time_limit(400)
def test_a_sambay_prompts_programs_call_no_kernel_and_fit_beside_the_state(
        one_chip):
    """The programs of a prompt at published widths: layers 0-16 over ONE
    block of positions after a state (a row alone, and the family's widest
    group), and the tails of the cell's two largest groups (the full layer
    but for its K and V and the cross-decoder at one token a row).  No
    Pallas call is in them and no loop over blocks, and beside the engine's
    resident state they hold less than the parent's whole-bucket programs
    did (13.994 / 13.488 GB)."""
    from ray_tpu.models.sambay import PHI4_MINI_FLASH

    eng, params = _sambay_engine(PHI4_MINI_FLASH)
    try:
        fam = eng.family
        assert fam.block % PHI4_MINI_FLASH.window == 0
        assert max(fam.prefill_width(b, eng.max_batch)
                   for b in (64, 512, 2048, 16384)) == 8
        held = {}
        for what in SAMBAY_PROMPT_BYTES:
            program, args = _block_program(eng, params, one_chip, what[1]) \
                if what[0] == "block" else \
                _tail_program(eng, params, one_chip, *what[1:])
            lowered = program.lower(*args)
            compiled = lowered.compile()
            assert KERNEL not in compiled.as_text()
            assert "flash" not in lowered.as_text()
            held[what] = _held_bytes(compiled, args[1:] if what[0] == "tail"
                                     else args, eng)
        assert all(held[k] < limit < 13.488e9
                   for k, limit in SAMBAY_PROMPT_BYTES.items()), held
    finally:
        eng.shutdown()


def test_a_carried_layer_is_compiled_once_a_width_not_once_a_bucket():
    """The host's loop at published widths over every bucket of the cell's
    engine, a full prompt a bucket as the warm-up sends them, alone and as a
    group, with each program traced abstractly once a signature (what a
    `jit` compiles once): the block's program has ONE signature a width
    (four: 1, 2, 4, 8 rows), however many buckets there are and however
    many blocks a prompt has (135 of them here at blocks of 512); only the tail, which
    holds no carried layer, is a program a (width, bucket)."""
    import numpy as np

    from ray_tpu.models.sambay import PHI4_MINI_FLASH, SambaYModel
    from ray_tpu.serve.llm_families import family_of

    fam = family_of(PHI4_MINI_FLASH, SAMBAY_ENGINE["max_len"])
    params = abstract_params(SambaYModel(PHI4_MINI_FLASH))
    seen = {"_fresh": {}, "_block": {}, "_tail": {}}
    calls = dict.fromkeys(seen, 0)

    def abstractly(name):
        program = getattr(fam, name)

        def call(*args):
            static = [a for a in args if isinstance(a, int)]
            traced = [a for a in args if not isinstance(a, int)]
            shapes = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), traced)
            key = (tuple(static), str(jax.tree_util.tree_structure(shapes)),
                   tuple((x.shape, str(x.dtype))
                         for x in jax.tree_util.tree_leaves(shapes)))
            calls[name] += 1
            if key not in seen[name]:
                seen[name][key] = jax.eval_shape(
                    lambda *t: program(*static, *t), *shapes)
            return seen[name][key]
        return call

    for name in seen:
        setattr(fam, name, abstractly(name))
    buckets = [64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384, 17472]
    groups = set()
    for bucket in buckets:
        for W in {1, fam.prefill_width(bucket, SAMBAY_ENGINE["max_batch"])}:
            groups.add((W, bucket))
            logits, fresh = fam.prefill_from_host(
                params, np.ones((W, bucket), np.int32),
                np.full((W,), bucket - 1, np.int32))
            assert logits.shape == (W, PHI4_MINI_FLASH.vocab_size)
            assert fresh["cache"][0].shape == (W, 10, bucket, 128)
    assert len(seen["_block"]) == len({W for W, _ in groups}) == 4
    assert len(seen["_tail"]) == len(groups) == 18
    assert calls["_block"] == sum(-(-b // fam.block) for _, b in groups)
    assert calls["_tail"] == calls["_fresh"] == len(groups)


@pytest.mark.slow     # 45 s of a many-threaded compile: by hand, not in tier-1
@pytest.mark.time_limit(600)
def test_sambay_engine_programs_fit_the_chip(one_chip):
    """The cell's decode chunk (eight paged calls a step, rings and
    recurrent state carried through the scan, a count of steps a slot)
    at published widths beside the weights and the engine's whole state
    (a prompt's programs:
    `test_a_sambay_prompts_programs_call_no_kernel_and_fit_beside_the_state`)."""
    from ray_tpu.models.sambay import PHI4_MINI_FLASH

    eng, params = _sambay_engine(PHI4_MINI_FLASH)
    try:
        decode = compiled_decode_chunk(eng, params, one_chip)
        assert decode.as_text().count(KERNEL) == 8
        _assert_the_pool_stays(decode.as_text(), eng)
        assert peak_bytes(decode) < HBM_BYTES
    finally:
        eng.shutdown()

