"""The MiniCPM-SALA family at the sizes of
`minicpmsala-serve-longdocs-closed`, compiled for a described v5e
(`tests/chip_compile.py` says how)."""

import jax.numpy as jnp
import pytest

import chip_smoke
from tests.chip_compile import (HBM_BYTES, KERNEL, NO_MOVES,  # noqa: F401
                                _compile_for_the_chip, abstract_params,
                                cell_config, compiled_decode_chunk,
                                compiled_prefill, gb, one_chip, paged_call,
                                peak_bytes, topo)

CELL = "minicpm-sala-l8.json"


def test_sala_paged_kernel_at_the_cells_shapes(one_chip):
    """The paged kernel as a sparse layer calls it: a row of its batch a
    (sequence, K/V head), 12 x 2 of them, 16 float32 query heads over ONE
    K/V head of 128; the pool one row a (page, head), 2 x (6,240 pages and
    the dummy); a GATHERED table of 128 columns (every page of the dense
    regime; 97 of them live in the sparse one); the step's rows written
    in place."""
    eng = cell_config(CELL)["serve"]["engine"]
    B, pages = eng["max_batch"], eng["kv_pool_tokens"] // 64 + 1
    compiled = paged_call(one_chip, 2 * B, 16, 1, 2 * pages, 128,
                          jnp.float32, writes=True)
    assert KERNEL in compiled.as_text()


@pytest.mark.time_limit(900)   # five programs of 8 layers: 100 s alone here
def test_sala_engine_programs_fit_the_chip_and_leave_the_pools(one_chip):
    """The cell's engine at published widths, built from the configuration
    file: ONE decode program (two paged calls a step, one a sparse layer;
    the selection, the compressed keys' write and six state steps in plain
    XLA around them) and ONE prefill program a bucket, at 4,096 and 8,192
    through the flash kernel, at 16,384 and 32,768 in the masked form with
    none; each holds the bytes the file's `memory` records, the largest
    prefill fits beside the resident state, and the decode chunk neither
    copies nor moves a K/V pool (the float32 compressed keys, 26 MB a layer,
    and the lightning state, 25 MB a layer, are prefetched to fast memory a
    step, as weights are: no copy of either)."""
    from benchmarks.families import minicpm_sala as family
    from ray_tpu.models.minicpm_sala import MiniCpmSalaModel
    from ray_tpu.serve.llm import LLMEngine

    conf = cell_config(CELL)
    cfg = family.program_config(family.sizes(conf))
    params = abstract_params(MiniCpmSalaModel(cfg))
    eng = LLMEngine(cfg, params, **conf["serve"]["engine"])
    try:
        memory = conf["memory"]
        assert gb(params) == pytest.approx(memory["weights_gb"], abs=1e-3)
        assert gb(eng._pools) == pytest.approx(memory["state_gb"]["all"],
                                               abs=1e-3)
        assert eng.family.state_bytes_per_slot == \
            memory["state_bytes_per_slot"]
        assert eng._tables.shape == (12, 521)
        decode = compiled_decode_chunk(eng, params, one_chip)
        text = decode.as_text()
        assert text.count(KERNEL) == 2
        assert chip_smoke.state_moves(text, eng._pools["pools"]) == NO_MOVES
        for name in ("cpools", "lightning"):
            moves = chip_smoke.state_moves(text, eng._pools[name])
            assert moves["loop"]["copy"] == moves["outside"]["copy"] == 0
        assert peak_bytes(decode) / 1e9 == pytest.approx(
            memory["decode_chunk_paged_gb"]["peak_with_weights_and_state"],
            abs=0.05)
        for bucket, kernels in ((4096, 2), (8192, 2), (16384, 0),
                                (32768, 0)):
            assert eng.family.prefill_width(bucket, eng.max_batch) == 1
            _, prefill = compiled_prefill(eng, params, one_chip, 1, bucket)
            assert prefill.as_text().count(KERNEL) == kernels
            # (the state is not an argument of the prefill: it is resident)
            resident = peak_bytes(prefill) / 1e9 + gb(eng._pools)
            assert resident == pytest.approx(
                memory[f"prefill_one_{bucket}_gb"][
                    "peak_with_state_resident"], abs=0.05)
            assert resident * 1e9 < 15.75e9 < HBM_BYTES
    finally:
        eng.shutdown()
