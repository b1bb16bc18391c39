"""The `lfm2_moe` family's part of the benchmark: its configuration file
against the catalog's keys, its cost functions by hand, its readers on
hand-made observations and span files, its reference against its model,
and a CPU rehearsal of `lfm2moe-serve-agents-closed` at tiny widths through
the harness's own closed-loop driver.  Every entry of `BENCHMARK.json` is
looked up by NAME and what the cell reports is compared as a superset, so
that the next cell to be appended needs no fixture to hide it from this
module (`tests/conftest.py` has the one the two older cell modules need).
"""

import json
import os
import sys
import time

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)
_HERE = os.path.dirname(os.path.abspath(__file__))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import kernel_costs, loader  # noqa: E402

CELL = "lfm2moe-serve-agents-closed"
CONFIG = "lfm2-24b-a2b-l9"
BENCH = loader.load_benchmark()
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
PERIOD = ["full_attention", "conv", "conv", "conv"]
PUBLISHED_TYPES = ["conv", "conv"] + PERIOD * 9 + ["full_attention", "conv"]
NEW_METRICS = {"moe_decode_roofline": ("model step", "device_trace", "higher"),
               "moe_prefill_mfu": ("model step", "device_trace", "higher"),
               "experts_touched_share": ("engine", "program_counter",
                                         "lower"),
               "moe_gmm_roofline": ("kernels", "device_trace", "higher"),
               "moe_paged_attn_roofline": ("kernels", "device_trace",
                                           "higher")}
CLOSED = {"batch_occupancy.closed", "prefill_device_ms.closed",
          "decode_step_ms.closed", "device_idle.closed",
          "queue_wait_ms.closed", "loop_host_ms.closed",
          "admit_host_ms.closed", "paged_live_share.closed"}


def _named(entries, name):
    return next(e for e in entries if e["name"] == name)


@pytest.fixture(scope="module")
def cell():
    return loader.load_cell(CELL)


@pytest.fixture(scope="module")
def costs(cell):
    return cell.readers["moe_decode_roofline"].costs


# ---- the configuration, the mix and the cell, as the issue names them ------


def test_the_cell_is_as_named(cell):
    assert cell.chips == 1 and cell.family_name == "lfm2_moe"
    conf = cell.config
    assert conf["reduced"] == ["num_hidden_layers", "num_dense_layers"]
    assert conf["published"] == {"num_hidden_layers": 40,
                                 "num_dense_layers": 2,
                                 "layer_types": PUBLISHED_TYPES}
    assert (conf["num_hidden_layers"], conf["num_dense_layers"]) == (9, 1)
    assert conf["layer_types"] == PUBLISHED_TYPES[1:10] == \
        ["conv"] + PERIOD * 2
    assert conf["source"] == SOURCE
    assert conf["deployment"]["chips_sharing_a_layer"] == 1
    mix = cell.traffic
    assert (mix["kind"], mix["clients"], mix["pool_requests"]) == \
        ("serve_closed", 16, 768)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 0.8, "min": 128, "max": 4096}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 128,
                                    "max": 384}
    assert mix["sampling"] == "greedy" and mix["shared_prefixes"] is False
    others = [json.load(open(os.path.join(_REPO, "benchmarks", "traffic", f)))
              for f in os.listdir(os.path.join(_REPO, "benchmarks",
                                               "traffic"))
              if f != "agents-closed.json"]
    assert mix["order_seed"] not in [t["order_seed"] for t in others]
    engine = conf["serve"]["engine"]
    # the mix's longest prompt, longest answer and one chunk, whole pages
    assert engine == {"max_batch": 16, "max_len": 4544, "page_size": 64,
                      "decode_chunk": 8, "kv_pool_tokens": 16 * 4544}
    assert 4096 + 384 + 8 <= engine["max_len"] < 4096 + 384 + 8 + 64
    assert mix["clients"] == engine["max_batch"]
    assert conf["serve"]["max_concurrency"] >= 24
    # a superset: what a later PR lists this cell under is its to add
    assert {m["name"] for m in cell.end_to_end} >= {"batch_tokens_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} >= \
        CLOSED | set(NEW_METRICS) | {"worker_ready_s"}
    loader.check_configuration(conf, cell.family)
    # every † point of the issue, the initialiser and the sizing
    assert {"tied_head", "conv_activation", "router", "weights",
            "torch_dtype", "sampling", "max_batch", "max_len",
            "kv_pool_tokens", "routing_tie"} <= set(conf["assumed"])
    assert "float32" in conf["precision"]["router"]
    assert "two bfloat16 terms" in conf["precision"]["activations"]
    assert conf["memory"]["weights_gb"] == pytest.approx(10.358, abs=1e-3)


def test_the_benchmark_holds_the_cell_by_name():
    """The configuration, the cell, its mix and its four metrics are in
    `BENCHMARK.json` under the issue's names; the cell's name is in the
    `workloads` of `batch_tokens_per_s` and of the eight `.closed` readers
    and of no metric that another kind of cell reports."""
    config = _named(BENCH["configs"], CONFIG)
    assert config == {
        "name": CONFIG, "source": SOURCE,
        "file": "benchmarks/configs/lfm2-24b-a2b-l9.json",
        "reduced": ["num_hidden_layers", "num_dense_layers"],
        "why": config["why"]}
    assert _named(BENCH["workloads"], CELL) == {
        "name": CELL, "config": CONFIG, "traffic": "agents-closed",
        "chips": 1, "why": _named(BENCH["workloads"], CELL)["why"]}
    for entry in (config, _named(BENCH["workloads"], CELL)):
        assert len(entry["why"]) <= 200
    for name, (layer, source, better) in NEW_METRICS.items():
        assert _named(BENCH["per_layer"], name) == {
            "name": name, "unit": "%", "better": better, "source": source,
            "layer": layer, "moves": "batch_tokens_per_s",
            "workloads": _named(BENCH["per_layer"], name)["workloads"]}
        assert CELL in _named(BENCH["per_layer"], name)["workloads"]
    listed = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed >= CLOSED | set(NEW_METRICS) | {"batch_tokens_per_s"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if m["name"] in listed:
            assert m.get("moves", "batch_tokens_per_s") == \
                "batch_tokens_per_s"
    # other families' yardsticks are not this cell's
    for name in ("paged_attn_roofline", "ssm_decode_roofline",
                 "shared_kv_attn_roofline", "ssm_prefill_mfu"):
        assert CELL not in _named(BENCH["per_layer"], name)["workloads"]


def test_the_file_holds_the_published_keys():
    """Every key of the catalog's copy of the published config.json, under
    the same name with the same value, but the two the cut changes and the
    list that goes with them; the catalog is the guide's, outside the
    repository, so where it is not there the file's own numbers are held
    to the ones the issue gives."""
    with open(os.path.join(_REPO, "benchmarks", "configs",
                           "lfm2-24b-a2b-l9.json")) as f:
        conf = json.load(f)
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-5, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 64,
        "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536, "num_hidden_layers": 40,
        "num_dense_layers": 2, "layer_types": PUBLISHED_TYPES}
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-24B-A2B")
        assert {k: row["config"][k] for k in published} == published
        published = row["config"]
        assert conf["source"] == row["source_url"]
    cut = ("num_hidden_layers", "num_dense_layers", "layer_types")
    assert {k: conf[k] for k in published if k not in cut} == \
        {k: v for k, v in published.items() if k not in cut}
    assert conf["published"] == {k: published[k] for k in cut}
    assert sorted(conf["reduced"]) == sorted(cut[:2])


def test_a_file_of_the_family_is_held_to_its_own_rules(cell):
    conf = cell.config
    for change, why in (
            ({"num_attention_heads": 16}, "heads of 128"),
            ({"hidden_size": 4096}, "heads of 128"),
            ({"conv_bias": True}, "no bias"),
            ({"use_expert_bias": False}, "expert_bias"),
            ({"norm_topk_prob": False}, "renormalises"),
            ({"layer_types": conf["layer_types"][:8]},
             "each of num_hidden_layers"),
            # the same kinds in another order: not the published entries
            ({"layer_types": ["conv"] + ["conv", "conv", "conv",
                                        "full_attention"] * 2},
             "entries 1-9 of the published list"),
            ({"published": dict(conf["published"],
                                moe_intermediate_size=2048)},
             "every width is the published one")):
        with pytest.raises(ValueError, match=why):
            cell.family.check_file(dict(conf, **change))
    assert cell.family.REDUCIBLE == {"num_hidden_layers", "num_dense_layers"}
    assert cell.family.DEPTH_KEY == "num_hidden_layers"
    assert cell.family.layer_pattern(conf) == (1, 4)
    for key, value in (("moe_intermediate_size", 2048), ("num_experts", 128),
                       ("num_experts_per_tok", 8), ("vocab_size", 131072)):
        with pytest.raises(loader.BenchmarkError, match="lets only"):
            loader.check_configuration(
                dict(conf, reduced=conf["reduced"] + [key],
                     published=dict(conf["published"], **{key: value})),
                cell.family)
    # a cut keeps a whole period and four routed layers: 5 would pass the
    # guide's floor, 4 would not
    with pytest.raises(loader.BenchmarkError, match="whole period"):
        loader.check_configuration(
            dict(conf, num_hidden_layers=4,
                 layer_types=conf["layer_types"][:4]), cell.family)
    loader.check_configuration(
        dict(conf, num_hidden_layers=5, layer_types=conf["layer_types"][:5]),
        cell.family)


def test_the_parameter_count_from_the_file_is_5_18_billion(cell, costs):
    from ray_tpu.models.lfm2_moe import count_params

    sizes = cell.family.sizes(cell.config)
    cfg = cell.family.program_config(sizes)
    counts = count_params(cfg)
    assert counts["total"] == 5_177_950_976 == costs.parameters(sizes)
    # the issue's arithmetic, a part at a time
    assert counts["conv"] == 3 * 2048 ** 2 + 2048 ** 2 + 3 * 2048
    assert counts["full_attention"] == 10_485_760 + 128
    assert counts["expert"] == 9_437_184 and counts["router"] == 131_136
    assert counts["dense_ffn"] == 72_351_744
    assert counts["embedding"] == 134_217_728
    assert costs.weight_bytes(sizes) == 10_358_078_464     # norms, router f32
    assert costs.layers(sizes) == {"conv": 7, "full_attention": 2,
                                   "dense": 1, "routed": 8}
    assert cfg.head_dim == 64 and cfg.n_expert_layers == 8
    # what the published model would be, uncut
    whole = cell.family.program_config(dict(
        sizes, **cell.config["published"]))
    assert count_params(whole)["total"] == 23_843_661_440


# ---- cost functions by hand -------------------------------------------------

CONV, ATTN, DENSE = 4 * 2048 ** 2, 10_485_760, 3 * 2048 * 11776
EXPERT, ROUTER = 3 * 2048 * 1536, 2048 * 64
TOKEN = 2 * (7 * CONV + 2 * ATTN + DENSE + 8 * (ROUTER + 4 * EXPERT)) \
    + 7 * 2 * 3 * 2048
HEAD = 2 * 65536 * 2048


def test_costs_by_hand(cell, costs):
    sizes = cell.family.sizes(cell.config)
    assert costs.matmul_params(sizes) == {
        "conv": CONV, "full_attention": ATTN, "dense": DENSE,
        "expert": EXPERT, "router": ROUTER}
    assert costs.kv_bytes_per_token(sizes) == 4096
    assert costs.conv_bytes_per_sequence(sizes) == 7 * 2 * 2048 * 4 == 114_688
    assert costs.expert_bytes(sizes) == 18_874_368
    assert costs.other_bytes(sizes) == 10_358_078_464 - 8 * 64 * 18_874_368 \
        == 694_402_048
    assert costs.token_flops(sizes) == TOKEN == 1_027_690_496
    # one step of 16 live rows holding 40,000 tokens that touched 41
    # experts in each of the eight routed layers
    flops, nbytes = costs.decode_step_cost(sizes, 16, 40_000, 8 * 41)
    assert flops == 16 * (TOKEN + HEAD) + 2 * 4 * 32 * 64 * 40_000
    assert nbytes == 694_402_048 + 328 * 18_874_368 + 40_000 * 4096 \
        + 16 * 114_688
    peak = kernel_costs.peaks("TPU v5 lite")
    least, bound = kernel_costs.roofline_seconds(flops, nbytes, peak)
    assert bound == "memory" and least == pytest.approx(8.609e-3, rel=1e-3)
    # the experts are nine tenths of it; a program that streams all 64
    # of each layer needs half as long again
    assert 328 * 18_874_368 / nbytes == pytest.approx(0.878, abs=0.002)
    every = costs.decode_step_cost(sizes, 16, 40_000, 8 * 64)[1]
    assert every / nbytes == pytest.approx(1.493, abs=0.002)
    # nothing live: the weights that are no expert's alone
    assert costs.decode_step_cost(sizes, 0, 0, 0) == (0.0, 694_402_048)
    # a prompt of 1,024: 1.07 TFLOP; one more token: its own products and
    # its keys
    n = 1024
    want = TOKEN * n + 2 * 4 * 32 * 64 * n * (n + 1) / 2 + HEAD
    assert costs.prefill_flops(sizes, n) == pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(1.0612e12, rel=1e-3)
    more = costs.prefill_flops(sizes, 101) - costs.prefill_flops(sizes, 100)
    assert more == pytest.approx(TOKEN + 2 * 4 * 32 * 64 * 101, rel=1e-9)
    # the grouped products of a layer: 64 pairs over 41 experts
    flops, nbytes = costs.grouped_product_cost(sizes, 64, 41)
    assert flops == 2 * 64 * EXPERT
    assert nbytes == 41 * 18_874_368 + 64 * 4 * (2048 + 3072 + 1536 + 2048)
    assert kernel_costs.roofline_seconds(flops, nbytes, peak) == \
        (pytest.approx(0.9477e-3, rel=1e-3), "memory")
    # 4,096 pairs a layer (a prompt of 1,024): compute bounds it
    assert kernel_costs.roofline_seconds(
        *costs.grouped_product_cost(sizes, 4096, 64), peak)[1] == "memory"
    assert kernel_costs.roofline_seconds(
        *costs.grouped_product_cost(sizes, 65536, 64), peak)[1] == "compute"
    # a step's custom calls as the program makes them: each attention
    # layer's paged call, then two grouped products a routed layer
    order = costs.kernel_order(sizes)
    assert order == (["paged"] + ["grouped"] * 8) * 2
    step = [10.0 if kind == "paged" else 70.0 for kind in order]
    for shift in (0, 1, 9, 13):         # a slot that opens inside a step
        split = costs.split_kernel_calls((step * 5)[shift: shift + 72],
                                         sizes)
        assert split == {"paged": [10.0] * 8, "grouped": [70.0] * 64}
    assert costs.split_kernel_calls([], sizes) == {"paged": [],
                                                   "grouped": []}
    assert costs.paged_decode_cost(sizes, 16, 40_000) == (
        4.0 * 40_000 * 32 * 64, 2.0 * 40_000 * 8 * 64 * 2
        + 2.0 * 16 * 32 * 64 * 2)


# ---- the readers, on a made-up `obs` and span files made by hand ------------

SLOT = (10.0, 12.0)


def _span(sid, name, t0_s, dur_ms, **attrs):
    return {"id": sid, "parent": None, "name": name,
            "t0_ns": int(t0_s * 1e9), "dur_ns": int(dur_ms * 1e6), "tid": 1,
            "thread": "llm-engine", "attrs": attrs}


@pytest.fixture
def spans(tmp_path, monkeypatch):
    """A session whose engine counted: four chunks of 8 steps in the traced
    slot (one of them half empty), one before it and one after it in the
    window, one of another time."""
    monkeypatch.setenv("RAY_TPU_TEMP_DIR", str(tmp_path))
    logs = tmp_path / "session-a" / "logs"
    logs.mkdir(parents=True)
    chunk = dict(expert_slots=8 * 8 * 64, expert_rows_max=3)
    lines = [{"header": {"pid": 7, "label": "w", "time_s": 5000.0,
                         "mono_ns": int(8e9)}}] + [
        _span(1, "engine.decode.wait", 1.0, 90, experts_touched=4096,
              **chunk),
        _span(2, "engine.decode.wait", 9.0, 90, experts_touched=2000,
              **chunk),
        _span(3, "engine.decode.wait", 10.1, 90, experts_touched=2600,
              **chunk),
        _span(4, "engine.decode.wait", 10.6, 90, experts_touched=2640,
              **chunk),
        _span(5, "engine.decode.wait", 11.1, 90, experts_touched=2624,
              **chunk),
        _span(6, "engine.decode.wait", 11.6, 90, experts_touched=2632,
              **chunk),
        _span(7, "engine.decode.wait", 13.0, 90, experts_touched=1400,
              **chunk),
        _span(8, "engine.prefill", 10.3, 40, bucket=1024, rows=1, width=1,
              expert_rows_max=300, expert_rows=32768)]
    (logs / "spans-w1.jsonl").write_text(
        "".join(json.dumps(x) + "\n" for x in lines))
    return tmp_path


def _obs(cell, **over):
    sizes = cell.family.sizes(cell.config)
    obs = {"sizes": sizes, "config": cell.config, "family": "lfm2_moe",
           "max_batch": 16, "window": (8.0, 14.0),
           "peaks": kernel_costs.peaks("TPU v5 lite"),
           # (t, slots taken, queued, streams decoding, their tokens): one
           # slot awaits its prefill, and the readers count it out
           "samples": [(10.0 + i / 20, 16, 0, 15, 40_000)
                       for i in range(40)] + [(13.0, 2, 0, 2, 100)],
           "replica_spans": [
               {"prompt_len": 1024, "first": 10.9},
               {"prompt_len": 1024, "first": 11.4},
               {"prompt_len": 1024, "first": 12.2},     # past the slot
               {"prompt_len": 1024, "first": None}],
           "trace": {"window_mono_s": SLOT,
                     # 32 steps of a paged call and the two grouped
                     # products of four routed layers, twice; the slot
                     # opens five calls into a step
                     "kernel_ns": {"decode_chunk_paged": (
                         ([0.2e6] + [0.7e6, 0.4e6] * 4) * 2 * 33)[
                             5: 5 + 32 * 18]},
                     "program_ns": {"decode_chunk_paged": [96e6] * 4,
                                    "prefill_one": [0.04e9],
                                    "prefill_many": [0.06e9]}}}
    obs.update(over)
    return obs


def test_readers_on_hand_made_observations(cell, costs, spans):
    sizes = cell.family.sizes(cell.config)
    peak = kernel_costs.peaks("TPU v5 lite")
    roof, mfu, share, gmm, attn = (cell.readers[n] for n in (
        "moe_decode_roofline", "moe_prefill_mfu", "experts_touched_share",
        "moe_gmm_roofline", "moe_paged_attn_roofline"))
    # the slot's four chunks touched (2600 + 2640 + 2624 + 2632) / 32 = 328
    # experts a step: 41 a layer; 15 rows decode, 40,000 tokens resident
    assert roof.touched_per_step(_obs(cell), 8) == 328.0
    least = kernel_costs.roofline_seconds(
        *costs.decode_step_cost(sizes, 15, 40_000, 328), peak)[0]
    assert least == pytest.approx(8.609e-3, rel=1e-3)
    # chunks of 8 steps in 96 ms: 12 ms a step
    assert roof.read(_obs(cell)) == pytest.approx(100 * least / 12e-3,
                                                  rel=1e-9)
    assert 71 < roof.read(_obs(cell)) < 72
    # the window's six chunks: 13,896 of 6 x 4096
    assert share.read(_obs(cell)) == pytest.approx(
        100 * 13_896 / (6 * 4096), rel=1e-12)
    # two prompts of 1,024 (1.061 TFLOP each) in 0.1 s of prefill programs
    assert mfu.read(_obs(cell)) == pytest.approx(
        100 * 2 * 1.0612e12 / (0.1 * 197e12), rel=2e-3)
    # 32 steps of 18 kernels: the grouped products take 8 x 1.1 ms a step
    # (their least: 15 x 4 x 8 pairs over 328 experts), the two paged calls
    # 0.4 ms, each read by its own metric and told apart by their order
    grouped = kernel_costs.roofline_seconds(
        *costs.grouped_product_cost(sizes, 15 * 4 * 8, 328), peak)[0]
    paged = kernel_costs.roofline_seconds(
        *costs.paged_decode_cost(sizes, 16, 40_000), peak)[0]
    assert gmm.read(_obs(cell)) == pytest.approx(
        100 * grouped / 8.8e-3, rel=1e-9)
    assert 80 < gmm.read(_obs(cell)) < 90
    assert attn.read(_obs(cell)) == pytest.approx(
        100 * paged / 0.2e-3, rel=1e-9)
    assert 50 < attn.read(_obs(cell)) < 51
    # nothing to read: no trace, another family's cell, a trace without
    # the programs -- None, never an error
    for reader in (roof, mfu, gmm, attn):
        assert reader.read(_obs(cell, trace=None)) is None
        assert reader.read(_obs(cell, family="granite_hybrid")) is None
        assert reader.read(_obs(cell, family="dense_decoder")) is None
        assert reader.read(_obs(cell, trace={
            "window_mono_s": SLOT, "kernel_ns": {},
            "program_ns": {}})) is None
    for name, reader in cell.readers.items():
        if name in NEW_METRICS:
            m = _named(BENCH["per_layer"], name)
            assert (reader.LAYER, reader.UNIT, reader.MOVES) == \
                (m["layer"], m["unit"], m["moves"])


def test_readers_on_a_program_that_counts_nothing(cell, tmp_path,
                                                  monkeypatch):
    """The parent's program, or another family's: spans without the
    counters, or no span file at all.  Every new reader returns None."""
    monkeypatch.setenv("RAY_TPU_TEMP_DIR", str(tmp_path))
    for name in NEW_METRICS:
        if name not in ("moe_prefill_mfu",          # (read no span)
                        "moe_paged_attn_roofline"):
            assert cell.readers[name].read(_obs(cell)) is None
    logs = tmp_path / "session-b" / "logs"
    logs.mkdir(parents=True)
    lines = [{"header": {"pid": 7, "label": "w", "time_s": 5000.0,
                         "mono_ns": int(8e9)}},
             _span(3, "engine.decode.wait", 10.1, 90, active=4, steps=32,
                   pages_live=10, pages_table=100)]
    (logs / "spans-w1.jsonl").write_text(
        "".join(json.dumps(x) + "\n" for x in lines))
    for name in ("moe_decode_roofline", "experts_touched_share",
                 "moe_gmm_roofline"):
        assert cell.readers[name].read(_obs(cell)) is None
    assert cell.readers["paged_live_share.closed"].read(_obs(cell)) == 10.0


# ---- the reference against the model, and the rehearsal ---------------------


def _tiny_config():
    with open(os.path.join(_HERE, "lfm2_moe", "configs",
                           "tiny-lfm2-moe.json")) as f:
        return json.load(f)


def test_reference_agrees_with_the_family_model_at_tiny_widths():
    """float32 on the CPU, seeded weights from the family's own `init`: the
    program's whole forward against the plain reference, 2e-5 (at a width
    of 64 the family's initialiser gives logits within +-0.5; float32
    reordering moves them by under 1e-6)."""
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    family = loader.load_family("lfm2_moe")
    sizes = family.sizes(_tiny_config())
    cfg = family.program_config(sizes, attention="reference")
    model = family.model(cfg)
    params = model.init(jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))
    tokens = np.random.default_rng(3).integers(1, 256, size=(1, 41))
    got = np.asarray(model.apply(params, jnp.asarray(tokens)))[0]
    ref = family.reference
    want = np.asarray(ref.logits(params, sizes, tokens[0].tolist()))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    rows = [5, 40]
    np.testing.assert_allclose(
        np.asarray(ref.logits(params, sizes, tokens[0].tolist(), rows)),
        want[rows], atol=1e-6)
    # two levels of rounding, the second rounding more, and the routing
    # pass, which rounds nothing and moves only where a selection is near
    # a tie
    assert len(ref.ROUNDINGS) == 4 and ref.ROUTING_PASS == 3
    off = [np.abs(np.asarray(ref.logits(
        params, sizes, tokens[0].tolist(), rounded=level)) - want).max(-1)
        for level in (1, 2)]
    assert 1e-7 < off[0].max() < 0.05 and 1e-7 < off[1].max() < 0.05
    scores: list = []
    ref.hidden_states(params, sizes, tokens[0].tolist(), scores=scores)
    assert len(scores) == 6 and scores[0].shape == (41, 8)
    top = -np.sort(-np.asarray(scores), axis=-1)          # (6, 41, 8)
    margin = (top[:, :, 1] - top[:, :, 2]).min(0)         # a position's
    # with the limit just over the narrowest margin there is ONE near-tie:
    # nothing moves before its position (the model is causal), it does
    at = int(np.argmin(margin))
    plain = lambda: np.asarray(ref._head(params, sizes, ref._streams(  # noqa: E731
        params, sizes, tokens[0].tolist(), 3)[0], None, 0))
    with mock_tie(ref, float(margin[at]) * 1.01):
        moved = np.abs(plain() - want).max(-1)
        assert moved[:at].max(initial=0.0) < 1e-6 < moved[at]
    with mock_tie(ref, 1.0):    # every selection is one: every position
        exchanged = plain()     # moves, by the exchange itself
        level = np.array(ref.logits(
            params, sizes, tokens[0].tolist(), rounded=3))
    assert np.abs(exchanged - want).max(-1).min() > 1e-4
    assert not hasattr(ref, "EXCHANGE_WEIGHT")
    # what the level hands the harness: the sound logits, the exchanged
    # pass's best token set as far over the sound best as it lies under it,
    # so that the harness's reading (how far the reference's own best falls
    # under the level's best) is that token's gap in the reference
    own, other = want.argmax(-1), exchanged.argmax(-1)
    rows = np.arange(len(want))
    np.testing.assert_allclose(
        level.max(-1) - level[rows, own],
        want[rows, own] - want[rows, other], atol=1e-6)
    level[rows, other] = want[rows, other]
    np.testing.assert_array_equal(level, want)
    # by hand: the exchanged pass prefers token 2, which lies 0.8 under the
    # sound best: set 0.8 over it; the same best token in both: nothing
    np.testing.assert_allclose(np.asarray(ref.standing_of_the_other(
        jnp.asarray([[1.0, 0.5, 0.2], [0.3, 0.9, 0.1]]),
        jnp.asarray([[0.1, 0.5, 0.9], [0.2, 0.8, 0.1]]))),
        [[1.0, 0.5, 1.8], [0.3, 0.9, 0.1]], atol=1e-7)
    loss = ref.mean_token_loss(
        params, sizes, [tokens[0, :-1].tolist()], [tokens[0, 1:].tolist()])
    assert loss == pytest.approx(float(family.loss(
        jnp.asarray(got[None, :-1]), jnp.asarray(tokens[:, 1:]))), abs=1e-4)
    with pytest.raises(ValueError, match="heads of 16"):
        family.check_file(_tiny_config())


def test_the_routing_pass_takes_the_sound_passes_other_set(cell):
    """A tie is decided in the SOUND pass and handed to the routing pass,
    whose own scores (moved by earlier exchanges) may stand the other way
    round: exchanging them there would take the sound pass's set again
    (on the chip two of three refused positions were missed so, PR 42)."""
    import jax.numpy as jnp

    ref = cell.family.reference
    d, E, k = 8, 6, 2
    rng = np.random.default_rng(0)
    p = {"router": jnp.asarray(rng.normal(size=(d, E)), jnp.float32),
         "expert_bias": jnp.zeros((E,), jnp.float32)}
    h = jnp.asarray(rng.normal(size=(3, d)), jnp.float32)
    g, biased, (near, swapped) = ref._gates(h, p, top_k=k)
    order = np.argsort(-np.asarray(biased), -1)
    assert (np.asarray(g) > 0).sum(-1).tolist() == [k] * 3
    np.testing.assert_array_equal(np.sort(np.asarray(swapped), -1),
                                  np.sort(order[:, [0, 2]], -1))
    assert not np.asarray(near).any()       # nothing within 1e-4 here
    # the sound pass says rows 0 and 2 were near a tie: the other set is
    # taken there, and row 1 takes its own, whatever THIS stream's scores
    # say (here: h with its sign turned, every order reversed)
    other = (jnp.asarray([True, False, True]), swapped)
    g2, biased2, _ = ref._gates(-h, p, top_k=k, other=other)
    took = [set(np.flatnonzero(row)) for row in np.asarray(g2) > 0]
    own = np.argsort(-np.asarray(biased2), -1)[:, :k]
    assert took == [set(order[0, [0, 2]]), set(own[1]),
                    set(order[2, [0, 2]])]
    np.testing.assert_allclose(np.asarray(g2).sum(-1), 1.0, atol=1e-5)


def test_the_follow_tool_at_tiny_widths(monkeypatch, tmp_path, capsys):
    """`tools/lfm2_moe_follow.py` on the tiny twin, every position counted
    as one to follow (a tolerance under zero): in float32 the followed
    pass gives the engine's tokens, takes the reference's experts
    everywhere, and with them forced lies where it lay; the CPU's
    reference is the same one."""
    from benchmarks.tools import lfm2_moe_follow as tool

    monkeypatch.setenv("FOLLOW_TINY", "1")
    monkeypatch.setattr(tool, "OUT", str(tmp_path))     # follow.jsonl
    family, config, mix = tool._cell()
    tool.follow(family, config, mix, 4200000401, 3, -1.0)
    said = {}
    for line in capsys.readouterr().out.splitlines():
        said.update(json.loads(line))
    assert said["judged"]["refused"] is False
    assert said["tokens"] == said["agree"] == len(said["over"]) \
        == said["natural_tokens_are_the_engines"] \
        == said["tokens_are_the_references"]
    assert said["top_k_is_exact"] is True
    assert said["positions_whose_selection_differs"] == 0
    assert said["logits_off_max"] < 1e-5
    assert said["selections_the_noise_alone_moves"] == 0
    assert said["cpu_logits_off_max"] == 0.0
    assert (tmp_path / "follow.jsonl").read_text().count(
        "\n") >= 8


def mock_tie(ref, tau):
    """The reference with another ROUTING_TIE (its `layer` traced anew)."""
    import contextlib
    from unittest import mock

    @contextlib.contextmanager
    def cm():
        with mock.patch.object(ref, "ROUTING_TIE", tau):
            ref.layer.clear_cache()
            try:
                yield
            finally:
                ref.layer.clear_cache()
    return cm()


def test_a_checkout_without_the_model_is_told_so_at_once(tmp_path,
                                                         monkeypatch):
    """The parent commit with these benchmark files laid over it: loading
    the family raises `BenchmarkError` (the command exits 1) before any
    cluster or replica is started."""
    monkeypatch.setattr(loader, "REPO_ROOT", str(tmp_path))
    with pytest.raises(loader.BenchmarkError,
                       match="no ray_tpu/models/lfm2_moe.py"):
        loader.load_family("lfm2_moe", _REPO, BENCH)


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """An in-process cluster that offers `TPU: 1` (conftest's seam gives
    such a lease-holder the CPU) and a benchmark whose one cell is the
    tiny `lfm2_moe` configuration under the tiny closed-loop mix,
    reporting what `lfm2moe-serve-agents-closed` reports."""
    import ray_tpu
    from tests.conftest import _fast_config

    root = tmp_path_factory.mktemp("lfm2_moe_rehearsal")
    bench = json.loads(json.dumps(BENCH))
    bench["paths"] = ["tests/benchmarks/lfm2_moe"]
    bench["configs"] = [{
        "name": "tiny-lfm2-moe", "source": "test", "reduced": [],
        "file": "tests/benchmarks/lfm2_moe/configs/tiny-lfm2-moe.json",
        "why": "test"}]
    bench["workloads"] = [{"name": "tiny.agents", "config": "tiny-lfm2-moe",
                           "traffic": "tiny-agents-closed", "chips": 1,
                           "why": "rehearsal"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.agents"] if CELL in m["workloads"] \
                else []
    os.symlink(os.path.join(_REPO, "tests"), root / "tests")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    ray_tpu.init(num_cpus=4, resources={"TPU": 1}, config=_fast_config())
    yield str(root)
    ray_tpu.shutdown()


@pytest.mark.time_limit(360)
@pytest.mark.parametrize("trace", [False, True], ids=["timed", "traced"])
def test_rehearsal_agents_closed(rehearsal, trace):
    """The whole of a run but the look for a chip: replica up through
    serve.run, every bucket warmed, 4 clients on 4 slots for 2 s, drained,
    samples against the reference (its routing pass among the roundings),
    nothing compiled in the window."""
    lines = []
    cell = loader.load_cell("tiny.agents", rehearsal)
    assert cell.family.__file__ == os.path.join(
        _REPO, "benchmarks", "families", "lfm2_moe.py")
    result = bench_run.run_cell(
        cell, 2 ** 31 + 11, 2.0, trace, time.monotonic(), platform="cpu",
        log=lambda **kw: lines.append(kw))
    assert result["correct"], lines
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    load = next(ln for ln in lines if ln.get("phase") == "load")
    assert load["compiles_in_window"] == 0
    # float32 on the CPU: the engine's tokens are the reference's argmax
    assert load["reference"] and all(
        c["max_logit_gap"] == 0.0 for c in load["reference"])
    if trace:
        # (no device plane on the CPU: the readers of the trace find
        # nothing and leave their metrics out; the counter's reader reads
        # the engine's spans, which are there)
        assert {"worker_ready_s", "batch_occupancy.closed",
                "experts_touched_share", "paged_live_share.closed"} <= \
            set(result["metrics"])
        assert not {"moe_decode_roofline", "moe_prefill_mfu",
                    "moe_gmm_roofline"} & set(result["metrics"])
        # four rows of top-2 of 8 touch 4-8 of a layer's experts
        assert 25 <= result["metrics"]["experts_touched_share"]["value"] \
            <= 100
    else:
        assert set(result["metrics"]) == {"batch_tokens_per_s", "setup_s"}
        assert result["metrics"]["batch_tokens_per_s"]["value"] > 0
