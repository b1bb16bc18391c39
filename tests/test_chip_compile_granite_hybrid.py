"""The Granite hybrid family at the sizes of `granite4h-serve-rows-closed`,
compiled for a described v5e (`tests/chip_compile.py` says how)."""

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from ray_tpu.ops import attention
from tests.chip_compile import (HBM_BYTES, KERNEL, NO_MOVES,  # noqa: F401
                                _compile_for_the_chip, abstract_params,
                                cell_config, compiled_decode_chunk,
                                compiled_prefill, gb, one_chip, paged_call,
                                peak_bytes, topo)


CELL = "granite-4.0-h-micro.json"


def test_granite_kernels_at_the_cells_shapes(one_chip):
    """Heads of 64 run as halves of heads of 128: 32 zero-padded query
    heads over 4 paired KV heads, scale 1/64.  The paged kernel over one
    attention layer's pool (max_batch x 25 pages of 64 and the dummy, a
    table of ceil((1600 + 8) / 64) = 26 columns, float32 queries, the
    step's rows written in place) and the flash kernel over the largest
    prefill, 8 rows of 1,024 tokens."""
    B = cell_config(CELL)["serve"]["engine"]["max_batch"]
    compiled = paged_call(one_chip, B, 32, 4, B * 25 + 1, 26, jnp.float32,
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

    conf = cell_config(CELL)
    cfg = family.program_config(family.sizes(conf))
    params = abstract_params(GraniteHybridModel(cfg))
    eng = LLMEngine(cfg, params, **conf["serve"]["engine"])
    try:
        B = eng.max_batch
        recorded = conf["memory"]["tried"][str(B)]
        assert gb(params) == pytest.approx(conf["memory"]["weights_gb"],
                                           abs=1e-3)
        assert gb(eng._pools) == pytest.approx(recorded["state_gb"]["all"],
                                               abs=1e-3)
        assert eng.family.state_bytes_per_slot == \
            conf["memory"]["recurrent_bytes_per_sequence"]
        decode = compiled_decode_chunk(eng, params, one_chip)
        text = decode.as_text()
        assert text.count(KERNEL) == 4
        conv, state = zip(*eng._pools["ssm"])
        assert chip_smoke.state_moves(text, eng._pools["pools"]) == NO_MOVES
        assert chip_smoke.state_moves(text, state) == NO_MOVES
        moves = chip_smoke.state_moves(text, conv)
        assert moves["loop"]["copy"] == moves["outside"]["copy"] == 0
        assert peak_bytes(decode) / 1e9 == pytest.approx(
            recorded["decode_chunk_paged_gb"]["peak_with_weights_and_state"],
            abs=0.05)
        assert eng.family.prefill_width(1024, B) == 8
        _, prefill = compiled_prefill(eng, params, one_chip, 8, 1024)
        assert KERNEL in prefill.as_text()
        # (the state is not an argument of the prefill: it is resident)
        resident = peak_bytes(prefill) / 1e9 + gb(eng._pools)
        assert resident == pytest.approx(
            recorded["prefill_many_8x1024_gb"]["peak_with_state_resident"],
            abs=0.05)
        assert resident * 1e9 < 15.75e9 < HBM_BYTES
    finally:
        eng.shutdown()

