"""The latent-attention family at the sizes of `kimivl-serve-pages-closed`,
compiled for a described v5e (`tests/chip_compile.py` says how)."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from ray_tpu.ops import paged_attention
from tests.chip_compile import (HBM_BYTES, KERNEL, NO_MOVES,  # noqa: F401
                                _compile_for_the_chip, abstract_params,
                                cell_config, compiled_decode_chunk,
                                compiled_prefill, gb, one_chip, peak_bytes,
                                shape_on, tiles_seen, topo)


@pytest.mark.parametrize("control", [
    None, "kernel_query_and_weights_in_one_bf16_term"])
def test_latent_kernel_at_the_cells_shapes(one_chip, control):
    """The latent-page kernel at the cell's shapes (64 rows, a table of
    146 pages, a pool of 5,633 pages of 64 rows of 640): Mosaic takes it,
    and the pool is aliased from input to output.  Also with the control
    that `benchmarks/tools/mla_moe_faults.py` plants in it on the chip
    (the query and the softmax's weights in one bfloat16 term)."""
    from benchmarks.tools.mla_moe_faults import planted

    S = shape_on(one_chip)
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
    from benchmarks.families import mla_moe as family
    from ray_tpu.models import mla_moe
    from ray_tpu.ops import grouped_matmul
    from ray_tpu.serve.llm import LLMEngine

    monkeypatch.setattr(grouped_matmul, "_interpret_mode", lambda: False)
    seen = tiles_seen(monkeypatch)
    conf = cell_config("kimi-vl-a3b-l7.json")
    cfg = family.program_config(family.sizes(conf))
    params = abstract_params(mla_moe.MlaMoeModel(cfg))
    engine = conf["serve"]["engine"]
    # (the engine's own pools are made on this machine's CPU: a page a
    # slot here, the cell's 5,633 pages as shapes below)
    eng = LLMEngine(cfg, params, **dict(engine, kv_pool_tokens=64 * 64))
    try:
        recorded = conf["memory"]
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
        decode = compiled_decode_chunk(eng, params, one_chip, pools)
        text = decode.as_text()
        assert text.count(KERNEL) == \
            recorded["decode_chunk_paged_gb"]["pallas_calls"] == 19
        assert chip_smoke.state_moves(text, pools) == NO_MOVES
        # 64 slots x 6 experts: 6 float32 rows a group, 64-row tiles, and
        # an expert's whole matrix a slab
        assert seen == 6 * [(384, 64, (64, 2048, 2816)),
                            (384, 64, (64, 1408, 2048))]
        peak = recorded["decode_chunk_paged_gb"]["peak_with_weights_and_state"]
        assert peak - 0.05 < peak_bytes(decode) / 1e9 < peak + 0.005
        del seen[:]
        assert eng.family.prefill_width(8192, B) == 1
        _, prefill = compiled_prefill(eng, params, one_chip, 1, 8192)
        assert prefill.as_text().count(KERNEL) == 19
        # 8,192 tokens, 768 float32 rows a group: the same tiles
        assert set(seen) == {(49152, 64, (64, 2048, 2816)),
                             (49152, 64, (64, 1408, 2048))}
        resident = peak_bytes(prefill) / 1e9 + gb(pools)
        # (the file records PR 45's 14.23 GB, over doubled rows; 14.15
        # from PR 47 until the row-wise operators ran in blocks of 256
        # positions, PR 50; 13.33 until the kernel moved a routed layer's
        # rows, PR 54: 12.82 now, and a ceiling from here on)
        assert resident < 12.87 < 13.33 < \
            14.15 < recorded["prefill_one_8192_gb"]["peak_with_state_resident"]
        # no pair row is gathered or gated outside a kernel: the first
        # product's (pairs, f) and the second's rows in their parts are
        # the custom calls' own, and nothing has a (pairs, d) or (pairs,
        # 2 f) float32 shape
        text = prefill.as_text()
        assert not re.search(r"f32\[49152,(2048|2816)\]", text)
        assert re.search(r"f32\[49152,1408\]\S* custom-call", text)
        assert re.search(r"f32\[786432,128\]\S* custom-call", text)
        assert resident * 1e9 < 15.75e9 < HBM_BYTES
    finally:
        eng.shutdown()
