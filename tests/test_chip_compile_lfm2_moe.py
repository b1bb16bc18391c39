"""The LFM2-MoE family at the sizes of `lfm2moe-serve-agents-closed`,
compiled for a described v5e (`tests/chip_compile.py` says how)."""

import re

import pytest

from tests.chip_compile import (HBM_BYTES, KERNEL,  # noqa: F401
                                _compile_for_the_chip, abstract_params,
                                cell_config, compiled_decode_chunk,
                                compiled_prefill, gb, one_chip, peak_bytes,
                                tiles_seen, topo)


@pytest.mark.time_limit(600)   # two programs of 9 layers: 60 s alone here
def test_lfm2_moe_engine_programs_fit_the_chip(one_chip, monkeypatch):
    """The cell's engine at published widths, built from the configuration
    file: the decode chunk (16 grouped products and 2 paged calls a step:
    18 Pallas calls, the experts' 9.7 GB resident and not copied) and the
    largest batched prefill (2 rows of 4,096 tokens: 65,536 (row, expert)
    pair rows of two terms through the grouped products) hold the bytes
    the file's `memory` records, beside 10.36 GB of weights."""
    from benchmarks.families import lfm2_moe as family
    from ray_tpu.models import lfm2_moe
    from ray_tpu.ops import grouped_matmul
    from ray_tpu.serve.llm import LLMEngine

    monkeypatch.setattr(grouped_matmul, "_interpret_mode", lambda: False)
    seen = tiles_seen(monkeypatch)
    conf = cell_config("lfm2-24b-a2b-l9.json")
    cfg = family.program_config(family.sizes(conf))
    params = abstract_params(lfm2_moe.Lfm2MoeModel(cfg))
    eng = LLMEngine(cfg, params, **conf["serve"]["engine"])
    try:
        recorded = conf["memory"]
        assert gb(params) == pytest.approx(recorded["weights_gb"], abs=1e-3)
        assert gb(eng._pools) == pytest.approx(recorded["state_gb"]["all"],
                                               abs=1e-3)
        assert eng.family.state_bytes_per_slot == \
            recorded["conv_window_bytes_per_sequence"]
        del seen[:]         # (the engine traced its programs' shapes)
        decode = compiled_decode_chunk(eng, params, one_chip)
        assert decode.as_text().count(KERNEL) == 18
        # 16 slots x 4 experts: 1 float32 row a group, one 64-row tile,
        # and an expert's whole matrix a slab
        assert seen == 8 * [(64, 64, (64, 2048, 3072)),
                            (64, 64, (64, 1536, 2048))]
        peak = recorded["decode_chunk_paged_gb"]["peak_with_weights_and_state"]
        assert peak - 0.05 < peak_bytes(decode) / 1e9 < peak + 0.005
        del seen[:]
        assert eng.family.prefill_width(4096, eng.max_batch) == 2
        _, prefill = compiled_prefill(eng, params, one_chip, 2, 4096)
        assert prefill.as_text().count(KERNEL) >= 18
        # 8,192 tokens, 512 float32 rows a group: the same tiles
        assert set(seen) == {(32768, 64, (64, 2048, 3072)),
                             (32768, 64, (64, 1536, 2048))}
        resident = peak_bytes(prefill) / 1e9 + gb(eng._pools)
        # (the file records PR 42's 13.12 GB, over doubled rows, and the
        # program still holds it, elsewhere: a ceiling since PR 54)
        assert resident < 13.17 and resident <= \
            recorded["prefill_many_2x4096_gb"]["peak_with_state_resident"]
        assert resident * 1e9 < 15.75e9 < HBM_BYTES
        # no pair row is gathered or gated outside a kernel
        text = prefill.as_text()
        assert not re.search(r"f32\[32768,(2048|3072)\]", text)
        assert re.search(r"f32\[32768,1536\]\S* custom-call", text)
        assert re.search(r"f32\[524288,128\]\S* custom-call", text)
    finally:
        eng.shutdown()

