"""The `granite_moe_hybrid` family's part of the benchmark: its configuration
file against the catalog's keys, its cost functions by hand, its readers on
hand-made observations and span files, its reference against its model (the
routing pass among its levels), and a CPU rehearsal of
`granite4hs-serve-desk-closed` at tiny widths, one chip's share of the
experts, through the harness's own closed-loop driver.  Every entry of
`BENCHMARK.json` is looked up by NAME and what the cell reports is compared
as a superset, as `test_lfm2_moe_cell.py` does.
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

CELL = "granite4hs-serve-desk-closed"
CONFIG = "granite-4.0-h-small-l10-e36"
FILE = "benchmarks/configs/granite-4.0-h-small-l10-e36.json"
BENCH = loader.load_benchmark()
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/" \
    "main/config.json"
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
NEW_METRICS = {
    "gmh_decode_roofline": ("model step", "device_trace", "higher"),
    "gmh_gmm_roofline": ("kernels", "device_trace", "higher"),
    "gmh_prefill_mfu": ("model step", "device_trace", "higher"),
    "expert_pairs_held_share": ("engine", "program_counter", "lower")}
CLOSED = {"batch_occupancy.closed", "prefill_device_ms.closed",
          "decode_step_ms.closed", "device_idle.closed",
          "queue_wait_ms.closed", "loop_host_ms.closed",
          "admit_host_ms.closed", "paged_live_share.closed",
          "experts_touched_share"}


def _named(entries, name):
    return next(e for e in entries if e["name"] == name)


@pytest.fixture(scope="module")
def cell():
    return loader.load_cell(CELL)


@pytest.fixture(scope="module")
def costs(cell):
    return cell.readers["gmh_decode_roofline"].costs


# ---- the configuration, the mix and the cell, as the issue names them ------


def test_the_cell_is_as_named(cell):
    assert cell.chips == 1 and cell.family_name == "granite_moe_hybrid"
    conf = cell.config
    assert conf["reduced"] == ["num_hidden_layers", "num_local_experts"]
    assert conf["published"] == {"num_hidden_layers": 40,
                                 "layer_types": PERIOD * 4,
                                 "num_local_experts": 72}
    assert (conf["num_hidden_layers"], conf["num_local_experts"],
            conf["num_experts_per_tok"]) == (10, 36, 10)
    assert conf["layer_types"] == PERIOD and conf["source"] == SOURCE
    deployment = conf["deployment"]
    assert (deployment["chips_sharing_a_layer"], deployment["chips_in_all"],
            deployment["experts_held"]) == (2, 8, [0, 36])
    mix = cell.traffic
    assert (mix["kind"], mix["clients"], mix["pool_requests"]) == \
        ("serve_closed", 48, 768)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 512,
                                    "sigma": 0.8, "min": 64, "max": 2048}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 256,
                                    "max": 768}
    assert mix["sampling"] == "greedy" and mix["shared_prefixes"] is False
    others = [json.load(open(os.path.join(_REPO, "benchmarks", "traffic", f)))
              for f in os.listdir(os.path.join(_REPO, "benchmarks",
                                               "traffic"))
              if f != "desk-closed.json"]
    assert mix["order_seed"] not in [t["order_seed"] for t in others]
    engine = conf["serve"]["engine"]
    # the mix's longest prompt, longest answer and one chunk, whole pages
    assert engine == {"max_batch": 48, "max_len": 2880, "page_size": 64,
                      "decode_chunk": 8, "kv_pool_tokens": 48 * 2880}
    assert 2048 + 768 + 8 <= engine["max_len"] < 2048 + 768 + 8 + 64
    assert mix["clients"] == engine["max_batch"]
    assert conf["serve"]["max_concurrency"] >= 72
    # a superset: what a later PR lists this cell under is its to add
    assert {m["name"] for m in cell.end_to_end} >= {"batch_tokens_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} >= \
        CLOSED | set(NEW_METRICS) | {"worker_ready_s"}
    loader.check_configuration(conf, cell.family)
    assert {"conv_activation", "dt_softplus", "gated_norm", "router",
            "router_precision", "no_auxiliary_loss", "weights", "the_share",
            "routing_tie", "sampling", "max_batch", "max_len",
            "kv_pool_tokens", "prefill_rows"} <= set(conf["assumed"])
    assert "float32" in conf["precision"]["router"]
    assert "two bfloat16 terms" in conf["precision"]["activations"]
    assert conf["precision"]["state"].startswith("float32")
    memory = conf["memory"]
    assert memory["weights_gb"] == pytest.approx(9.932, abs=1e-3)
    assert set(memory["tried"]) == {"64", "48", "32"}
    assert memory["chosen"] == "48"


def test_the_benchmark_holds_the_cell_by_name():
    """The configuration, the cell, its mix and its four metrics are in
    `BENCHMARK.json` under the issue's names; the cell's name follows the
    older cells' in the `workloads` of `batch_tokens_per_s`, of the eight
    `.closed` readers and of `experts_touched_share`, and is in those of no
    metric that another kind of cell reports; it takes one chip."""
    config = _named(BENCH["configs"], CONFIG)
    assert config == {"name": CONFIG, "source": SOURCE, "file": FILE,
                      "reduced": ["num_hidden_layers", "num_local_experts"],
                      "why": config["why"]}
    assert _named(BENCH["workloads"], CELL) == {
        "name": CELL, "config": CONFIG, "traffic": "desk-closed",
        "chips": 1, "why": _named(BENCH["workloads"], CELL)["why"]}
    for entry in (config, _named(BENCH["workloads"], CELL)):
        assert len(entry["why"]) <= 200
    for name, (layer, source, better) in NEW_METRICS.items():
        assert _named(BENCH["per_layer"], name) == {
            "name": name, "unit": "%", "better": better, "source": source,
            "layer": layer, "moves": "batch_tokens_per_s",
            "workloads": _named(BENCH["per_layer"], name)["workloads"]}
        assert _named(BENCH["per_layer"], name)["workloads"][0] == CELL
    listed = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed >= CLOSED | set(NEW_METRICS) | {"batch_tokens_per_s"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if m["name"] in listed:
            assert m.get("moves", "batch_tokens_per_s") == \
                "batch_tokens_per_s"
            assert m["workloads"].index(CELL) >= \
                m["workloads"].index("minicpmsala-serve-longdocs-closed") \
                if "minicpmsala-serve-longdocs-closed" in m["workloads"] \
                else True
    # other families' yardsticks are not this cell's
    for name in ("ssm_decode_roofline", "moe_decode_roofline",
                 "moe_gmm_roofline", "mla_gmm_roofline", "ssm_prefill_mfu"):
        assert CELL not in _named(BENCH["per_layer"], name)["workloads"]
    assert len(BENCH["workloads"]) >= 9


def test_the_file_holds_the_published_keys():
    """Every key of the catalog's copy of the published config.json, under
    the same name with the same value, but the two the cut changes and the
    list that goes with the depth; the catalog is the guide's, outside the
    repository, so where it is not there the file's own numbers are held
    to the ones the issue gives."""
    with open(os.path.join(_REPO, FILE)) as f:
        conf = json.load(f)
    published = {
        "hidden_size": 4096, "intermediate_size": 768,
        "shared_intermediate_size": 1536, "num_attention_heads": 32,
        "num_key_value_heads": 8, "mamba_n_heads": 128, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_d_conv": 4, "mamba_chunk_size": 256,
        "mamba_n_groups": 1, "mamba_expand": 2, "num_experts_per_tok": 10,
        "num_local_experts": 72, "num_hidden_layers": 40,
        "layer_types": PERIOD * 4, "vocab_size": 100352,
        "embedding_multiplier": 12, "residual_multiplier": 0.22,
        "attention_multiplier": 0.0078125, "logits_scaling": 16,
        "tie_word_embeddings": True, "position_embedding_type": "nope"}
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "granite-4.0-h-small")
        assert {k: row["config"][k] for k in published} == published
        published = row["config"]
        assert conf["source"] == row["source_url"]
    cut = ("num_hidden_layers", "num_local_experts", "layer_types")
    assert {k: conf[k] for k in published if k not in cut} == \
        {k: v for k, v in published.items() if k not in cut}
    assert conf["published"] == {k: published[k] for k in cut}
    assert sorted(conf["reduced"]) == sorted(cut[:2])


def test_a_file_of_the_family_is_held_to_its_own_rules(cell):
    conf = cell.config
    share = lambda **kw: dict(conf["deployment"], **kw)  # noqa: E731
    for change, why in (
            ({"num_attention_heads": 64}, "heads of 64"),
            ({"position_embedding_type": "rope"}, "no position term"),
            ({"mamba_n_groups": 8}, "one group"),
            ({"layer_types": conf["layer_types"][:8]},
             "each of num_hidden_layers"),
            ({"layer_types": ["attention"] + conf["layer_types"][1:]},
             "leading run of the published list"),
            ({"num_experts_per_tok": 0}, "routed member"),
            ({"deployment": share(experts_held=[40, 36])}, "a run of 36"),
            ({"deployment": share(experts_held=[0, 24])}, "a run of 36"),
            ({"deployment": share(chips_sharing_a_layer=4)},
             "do not make up the 72"),
            ({"deployment": {k: v for k, v in conf["deployment"].items()
                             if k != "experts_held"}}, "goes with"),
            ({"published": dict(conf["published"], intermediate_size=1024)},
             "every width is the published one")):
        with pytest.raises(ValueError, match=why):
            cell.family.check_file(dict(conf, **change))
    assert cell.family.REDUCIBLE == {"num_hidden_layers",
                                     "num_local_experts"}
    assert cell.family.EXPERTS_KEY == "num_local_experts"
    assert cell.family.layer_pattern(conf) == (0, 10)
    for key, value in (("intermediate_size", 1536), ("hidden_size", 8192),
                       ("num_experts_per_tok", 20), ("vocab_size", 200704)):
        with pytest.raises(loader.BenchmarkError, match="lets only"):
            loader.check_configuration(
                dict(conf, reduced=conf["reduced"] + [key],
                     published=dict(conf["published"], **{key: value})),
                cell.family)
    # a cut keeps a whole period of ten, and no fewer than 8 experts
    with pytest.raises(loader.BenchmarkError, match="whole period"):
        loader.check_configuration(
            dict(conf, num_hidden_layers=9,
                 layer_types=conf["layer_types"][:9]), cell.family)
    with pytest.raises(loader.BenchmarkError, match="the floor is 8"):
        loader.check_configuration(dict(conf, num_local_experts=6),
                                   cell.family)
    with pytest.raises(loader.BenchmarkError, match="chips_sharing_a_layer"):
        loader.check_configuration(
            dict(conf, deployment={"what": "one chip"}), cell.family)
    # the dense member's family refuses this file, and this one the micro's
    dense = loader.load_family("granite_hybrid")
    with pytest.raises(ValueError, match="heads of 128|routed experts"):
        dense.check_file(conf)


def test_the_parameter_count_from_the_file_is_4_96_billion(cell, costs):
    from ray_tpu.models.granite_hybrid import count_params

    sizes = cell.family.sizes(cell.config)
    assert sizes["router_experts"] == 72 and sizes["experts_held"] == [0, 36]
    cfg = cell.family.program_config(sizes)
    counts = count_params(cfg)
    assert counts["total"] == 4_962_732_672 == costs.parameters(sizes)
    # the issue's arithmetic, a part at a time
    assert counts["mamba"] == 461_203_072
    assert counts["attention"] == 400_859_136
    assert counts["expert"] == 9_437_184 and counts["router"] == 294_912
    assert counts["embedding"] == 411_041_792
    mm = costs.matmul_params(sizes)
    assert mm["mamba"] == 4096 * 16_768 + 8192 * 4096
    assert mm["attention"] == 41_943_040 and mm["shared"] == 18_874_368
    assert costs.layers(sizes) == {"mamba": 9, "attention": 1}
    assert costs.held(sizes) == 36
    assert (cfg.head_dim, cfg.paired, cfg.experts_here) == (128, False, 36)
    # what the published model would be, uncut: "32B"
    whole = cell.family.program_config(dict(
        sizes, experts_held=None, **cell.config["published"]))
    assert count_params(whole)["total"] == 36 * 800_941_696 \
        + 4 * 740_597_760 + 411_041_792 + 4096 == 32_207_337_984


# ---- cost functions by hand -------------------------------------------------

MAMBA, ATTN = 4096 * 16_768 + 8192 * 4096, 41_943_040
SHARED, EXPERT, ROUTER = 18_874_368, 9_437_184, 4096 * 72
TOKEN = 2 * (9 * MAMBA + ATTN + 10 * (SHARED + ROUTER)) \
    + 9 * (6 * 128 * 64 * 128 + 2 * 4 * 8448)
HEAD = 2 * 100_352 * 4096
STATE = 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)


def test_costs_by_hand(cell, costs):
    sizes = cell.family.sizes(cell.config)
    assert costs.matmul_params(sizes) == {
        "mamba": MAMBA, "attention": ATTN, "shared": SHARED,
        "expert": EXPERT, "router": ROUTER}
    assert costs.kv_bytes_per_token(sizes) == 4096
    assert costs.state_bytes_per_sequence(sizes) == STATE == 38_204_928
    assert costs.expert_bytes(sizes) == 18_874_368
    # float32: ten routers, twenty layer norms, nine gated norms and heads'
    # vectors, the last norm
    f32 = 10 * (ROUTER + 2 * 4096) + 9 * (3 * 128 + 8192) + 4096
    assert costs.float32_parameters(sizes) == f32
    assert costs.weight_bytes(sizes) == 2 * 4_962_732_672 + 2 * f32 \
        == 9_931_689_984
    assert costs.other_bytes(sizes) == 9_931_689_984 \
        - 10 * 36 * 18_874_368 == 3_136_917_504
    assert costs.token_flops(sizes) == TOKEN
    assert costs.pair_flops(sizes) == 2 * EXPERT
    # the issue's step: 43 live rows holding 39,000 tokens that touched
    # every held expert of every layer (360) with 215 pairs held
    flops, nbytes = costs.decode_step_cost(sizes, 43, 39_000, 360, 215)
    assert flops == 43 * (TOKEN + HEAD) + 215 * 2 * EXPERT \
        + 4 * 32 * 128 * 39_000
    assert nbytes == 3_136_917_504 + 360 * 18_874_368 + 2 * 43 * STATE \
        + 39_000 * 4096
    peak = kernel_costs.peaks("TPU v5 lite")
    least, bound = kernel_costs.roofline_seconds(flops, nbytes, peak)
    assert bound == "memory" and least == pytest.approx(16.33e-3, rel=2e-3)
    # the experts are half of it, the state a quarter
    assert 360 * 18_874_368 / nbytes == pytest.approx(0.508, abs=0.003)
    assert 2 * 43 * STATE / nbytes == pytest.approx(0.246, abs=0.003)
    # nothing live: the weights that are no expert's alone
    assert costs.decode_step_cost(sizes, 0, 0, 0, 0) == (0.0, 3_136_917_504)
    # a prompt of 512 without its routed pairs; one more token: its own
    # products and its keys
    n = 512
    want = TOKEN * n + 4 * 32 * 128 * n * (n + 1) / 2 + HEAD
    assert costs.prefill_flops(sizes, n) == pytest.approx(want, rel=1e-12)
    more = costs.prefill_flops(sizes, 101) - costs.prefill_flops(sizes, 100)
    assert more == pytest.approx(TOKEN + 4 * 32 * 128 * 101, rel=1e-9)
    # the grouped products of a step: 215 pairs over 360 touched experts
    flops, nbytes = costs.grouped_product_cost(sizes, 215, 360)
    assert flops == 215 * 2 * EXPERT
    assert nbytes == 360 * 18_874_368 + 215 * 4 * (4096 + 1536 + 768 + 4096)
    assert kernel_costs.roofline_seconds(flops, nbytes, peak) == \
        (pytest.approx(8.307e-3, rel=1e-3), "memory")
    # a step's custom calls as the program makes them: five layers' two
    # grouped products, the attention layer's paged call and its two,
    # four layers' more
    order = costs.kernel_order(sizes)
    assert order == ["w13", "w2"] * 5 + ["paged"] + ["w13", "w2"] * 5
    step = [{"w13": 560.0, "w2": 290.0, "paged": 210.0}[k] for k in order]
    for shift in (0, 1, 10, 13, 20):    # a slot that opens inside a step
        split = costs.split_kernel_calls((step * 6)[shift: shift + 105],
                                         sizes)
        assert sorted(split["paged"]) == [210.0] * 5
        assert sorted(split["grouped"]) == [290.0] * 50 + [560.0] * 50
    assert costs.split_kernel_calls([], sizes) == {"paged": [],
                                                   "grouped": []}


# ---- the readers, on a made-up `obs` and span files made by hand ------------

SLOT = (10.0, 12.0)


def _span(sid, name, t0_s, dur_ms, **attrs):
    return {"id": sid, "parent": None, "name": name,
            "t0_ns": int(t0_s * 1e9), "dur_ns": int(dur_ms * 1e6), "tid": 1,
            "thread": "llm-engine", "attrs": attrs}


@pytest.fixture
def spans(tmp_path, monkeypatch):
    """A session whose engine counted: four chunks of 8 steps in the traced
    slot, one before it and one after it in the window; two prefills in the
    slot."""
    monkeypatch.setenv("RAY_TPU_TEMP_DIR", str(tmp_path))
    logs = tmp_path / "session-a" / "logs"
    logs.mkdir(parents=True)
    chunk = dict(expert_slots=8 * 10 * 36, expert_rows_max=11,
                 expert_pairs=8 * 430 * 10)
    wait = lambda sid, t, touched, held: _span(  # noqa: E731
        sid, "engine.decode.wait", t, 170, experts_touched=touched,
        expert_pairs_held=held, **chunk)
    lines = [{"header": {"pid": 7, "label": "w", "time_s": 5000.0,
                         "mono_ns": int(8e9)}}] + [
        wait(1, 9.0, 2880, 17_000), wait(2, 10.1, 2872, 17_100),
        wait(3, 10.6, 2880, 17_300), wait(4, 11.1, 2876, 17_200),
        wait(5, 11.6, 2880, 17_200), wait(6, 13.0, 2000, 9_000),
        _span(8, "engine.prefill.wait", 10.3, 40, expert_rows_max=300,
              expert_rows=25_000),
        _span(9, "engine.prefill.wait", 11.2, 40, expert_rows_max=200,
              expert_rows=27_000)]
    (logs / "spans-w1.jsonl").write_text(
        "".join(json.dumps(x) + "\n" for x in lines))
    return tmp_path


def _obs(cell, **over):
    sizes = cell.family.sizes(cell.config)
    step = [{"w13": 0.56e6, "w2": 0.29e6, "paged": 0.21e6}[k]
            for k in cell.readers["gmh_decode_roofline"].costs
            .kernel_order(sizes)]
    obs = {"sizes": sizes, "config": cell.config,
           "family": "granite_moe_hybrid", "max_batch": 48,
           "window": (8.0, 14.0),
           "peaks": kernel_costs.peaks("TPU v5 lite"),
           # (t, slots taken, queued, streams decoding, their tokens)
           "samples": [(10.0 + i / 20, 48, 0, 43, 39_000)
                       for i in range(40)] + [(13.0, 2, 0, 2, 100)],
           "replica_spans": [
               {"prompt_len": 512, "first": 10.9},
               {"prompt_len": 512, "first": 11.4},
               {"prompt_len": 512, "first": 12.2},      # past the slot
               {"prompt_len": 512, "first": None}],
           "trace": {"window_mono_s": SLOT,
                     # 32 steps of 21 calls; the slot opens 7 calls in
                     "kernel_ns": {"decode_chunk_paged":
                                   (step * 33)[7: 7 + 32 * 21]},
                     "program_ns": {"decode_chunk_paged": [176e6] * 4,
                                    "prefill_one": [0.045e9],
                                    "prefill_many": [0.055e9]}}}
    obs.update(over)
    return obs


def test_readers_on_hand_made_observations(cell, costs, spans):
    sizes = cell.family.sizes(cell.config)
    peak = kernel_costs.peaks("TPU v5 lite")
    roof, gmm, mfu, share = (cell.readers[n] for n in NEW_METRICS)
    touched = cell.readers["experts_touched_share"]
    # the slot's four chunks: (2872 + 2880 + 2876 + 2880) / 32 = 359.6
    # held experts touched a step, 68,800 / 32 = 2,150 held pairs
    assert roof.counted_per_step(_obs(cell), 8) == (359.625, 2150.0)
    least = kernel_costs.roofline_seconds(
        *costs.decode_step_cost(sizes, 43, 39_000, 359.625, 2150.0), peak)[0]
    # chunks of 8 steps in 176 ms: 22 ms a step
    assert roof.read(_obs(cell)) == pytest.approx(100 * least / 22e-3,
                                                  rel=1e-9)
    assert 70 < roof.read(_obs(cell)) < 80
    # the window's six chunks: held pairs over pairs, touched over slots
    assert share.read(_obs(cell)) == pytest.approx(
        100 * 94_800 / (6 * 34_400), rel=1e-12)
    assert touched.read(_obs(cell)) == pytest.approx(
        100 * 16_388 / (6 * 2880), rel=1e-12)
    # two prompts of 512 and the 52,000 pairs the slot's prefills held,
    # in 0.1 s of prefill programs
    want = 2 * costs.prefill_flops(sizes, 512) + 52_000 * 2 * EXPERT
    assert mfu.read(_obs(cell)) == pytest.approx(
        100 * want / (0.1 * 197e12), rel=1e-9)
    assert 10 < mfu.read(_obs(cell)) < 25
    # 32 steps of 21 kernels: the grouped products take 10 x 0.85 ms a
    # step, told from the paged call by where they stand
    grouped = kernel_costs.roofline_seconds(
        *costs.grouped_product_cost(sizes, 2150.0, 359.625), peak)[0]
    assert gmm.read(_obs(cell)) == pytest.approx(
        100 * grouped / 8.5e-3, rel=1e-9)
    assert 95 < gmm.read(_obs(cell)) < 100
    # nothing to read: no trace, another family's cell, a trace without
    # the programs -- None, never an error
    for reader in (roof, gmm, mfu):
        assert reader.read(_obs(cell, trace=None)) is None
        assert reader.read(_obs(cell, family="granite_hybrid")) is None
        assert reader.read(_obs(cell, family="lfm2_moe")) is None
        assert reader.read(_obs(cell, trace={
            "window_mono_s": SLOT, "kernel_ns": {},
            "program_ns": {}})) is None
    # the older routed cells' readers find nothing in this family's cell
    for name in ("moe_decode_roofline", "moe_gmm_roofline",
                 "ssm_decode_roofline"):
        assert loader.sibling_reader(
            costs.__file__, name).read(_obs(cell)) is None
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
        assert cell.readers[name].read(_obs(cell)) is None
    logs = tmp_path / "session-b" / "logs"
    logs.mkdir(parents=True)
    lines = [{"header": {"pid": 7, "label": "w", "time_s": 5000.0,
                         "mono_ns": int(8e9)}},
             _span(3, "engine.decode.wait", 10.1, 90, active=4, steps=32,
                   pages_live=10, pages_table=100, experts_touched=5,
                   expert_slots=10),
             _span(4, "engine.prefill.wait", 10.2, 90, bucket=64)]
    (logs / "spans-w1.jsonl").write_text(
        "".join(json.dumps(x) + "\n" for x in lines))
    for name in NEW_METRICS:
        assert cell.readers[name].read(_obs(cell)) is None
    assert cell.readers["paged_live_share.closed"].read(_obs(cell)) == 10.0
    assert cell.readers["experts_touched_share"].read(_obs(cell)) == 50.0


# ---- the reference against the model, and the rehearsal ---------------------


def _tiny_config():
    with open(os.path.join(_HERE, "granite_moe_hybrid", "configs",
                           "tiny-granite-moe-hybrid.json")) as f:
        return json.load(f)


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


def test_reference_agrees_with_the_family_model_at_tiny_widths():
    """float32 on the CPU, seeded weights from the family's own `init`, the
    share of the tiny file (experts 0-3 of 8): the program's whole forward
    against the plain reference, 2e-5 (at tiny widths the family's
    initialiser gives small logits; float32 reordering moves them by under
    1e-6); the three levels of rounding, and the routing pass, which rounds
    nothing and moves only where a selection is near a tie AND one of the
    two experts is held."""
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    family = loader.load_family("granite_moe_hybrid")
    sizes = family.sizes(_tiny_config())
    assert sizes["router_experts"] == 8 and sizes["experts_held"] == [0, 4]
    cfg = family.program_config(sizes, attention="reference")
    assert (cfg.n_experts, cfg.experts_held, cfg.top_k) == (8, (0, 4), 3)
    model = family.model(cfg)
    params = model.init(jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))
    assert params["params"]["layers_0"]["experts"]["w13"].shape == \
        (4, 512, 64)
    assert params["params"]["layers_0"]["experts"]["router"].shape == (512, 8)
    tokens = np.random.default_rng(3).integers(1, 256, size=(1, 41))
    got = np.asarray(model.apply(params, jnp.asarray(tokens)))[0]
    ref = family.reference
    want = np.asarray(ref.logits(params, sizes, tokens[0].tolist()))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    rows = [5, 40]
    np.testing.assert_allclose(
        np.asarray(ref.logits(params, sizes, tokens[0].tolist(), rows)),
        want[rows], atol=1e-6)
    assert len(ref.ROUNDINGS) == 5 and ref.ROUTING_PASS == 4
    assert ref.ROUNDINGS[:4] == ref.dense.ROUNDINGS
    off = [np.abs(np.asarray(ref.logits(
        params, sizes, tokens[0].tolist(), rounded=level)) - want).max()
        for level in (1, 2, 3)]
    assert 1e-7 < off[0] < off[2] < 0.5 and 1e-7 < off[1] < 0.5
    scores: list = []
    ref.hidden_states(params, sizes, tokens[0].tolist(), scores=scores)
    assert len(scores) == 8 and scores[0].shape == (41, 8)
    order = np.argsort(-np.asarray(scores), axis=-1)       # (8, 41, 8)
    top = np.take_along_axis(np.asarray(scores), order, -1)
    held = (order[:, :, 2:4] < 4).any(-1)
    margin = np.where(held, top[:, :, 2] - top[:, :, 3], np.inf).min(0)
    at = int(np.argmin(margin))
    plain = lambda: np.asarray(ref.rounded_logits(  # noqa: E731
        params, sizes, tokens[0].tolist(), rounded=ref.ROUTING_PASS))
    # with the limit just over the narrowest margin that matters there is
    # ONE near-tie: nothing moves before its position, it does
    with mock_tie(ref, float(margin[at]) * 1.01):
        moved = np.abs(plain() - want).max(-1)
        assert moved[:at].max(initial=0.0) < 1e-6 < moved[at]
    # a selection between two ABSENT experts is no tie, however near
    absent = np.where(~held, top[:, :, 2] - top[:, :, 3], np.inf)
    if absent.min() < margin[at]:
        with mock_tie(ref, float(absent.min()) * 1.01):
            assert np.abs(plain() - want).max() < 1e-6
    with mock_tie(ref, 10.0):   # every selection that matters is one
        exchanged = plain()
        level = np.array(ref.logits(params, sizes, tokens[0].tolist(),
                                    rounded=ref.ROUTING_PASS))
    assert np.abs(exchanged - want).max(-1).min() > 1e-5
    own, other = want.argmax(-1), exchanged.argmax(-1)
    at_rows = np.arange(len(want))
    np.testing.assert_allclose(
        level.max(-1) - level[at_rows, own],
        want[at_rows, own] - want[at_rows, other], atol=1e-6)
    level[at_rows, other] = want[at_rows, other]
    np.testing.assert_array_equal(level, want)
    loss = ref.mean_token_loss(
        params, sizes, [tokens[0, :-1].tolist()], [tokens[0, 1:].tolist()])
    assert loss == pytest.approx(float(family.loss(
        jnp.asarray(got[None, :-1]), jnp.asarray(tokens[:, 1:]))), abs=1e-4)
    with pytest.raises(ValueError, match="mamba_expand x hidden_size"):
        family.check_file(_tiny_config())
    # the weights must hold what the configuration says is held
    with pytest.raises(ValueError, match="the weights hold 4 experts"):
        ref.logits(params, dict(sizes, experts_held=[0, 2]),
                   tokens[0].tolist())


def test_the_planted_faults_of_the_tool_show_at_tiny_widths():
    """`tools/granite_moe_hybrid_faults.py`'s own patches on the tiny twin's
    whole forward (the reused slot's stale state apart, which only an
    engine can show: `write_prompt` is checked by hand): each moves the
    logits (within +-0.5 here: the family's initialiser at a width of 64)
    by more than 2e-3, a thousand times what float32 reordering moves
    them, and leaving `planted` restores the program."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from benchmarks.tools import granite_moe_hybrid_faults as tool

    jax.config.update("jax_platforms", "cpu")
    family = loader.load_family("granite_moe_hybrid")
    sizes = family.sizes(_tiny_config())
    cfg = family.program_config(sizes, attention="reference")
    model = family.model(cfg)
    params = model.init(jax.random.PRNGKey(5), jnp.zeros((1, 8), jnp.int32))
    tokens = jnp.asarray(np.random.default_rng(5).integers(1, 256, (1, 33)))
    sound = np.asarray(model.apply(params, tokens))

    def run(name):
        from ray_tpu.models.granite_hybrid import GraniteHybridModel

        c = dataclasses.replace(cfg, **tool.CONFIG_FAULTS.get(name, {}))
        with tool.planted(name):
            return np.asarray(GraniteHybridModel(c).apply(params, tokens))

    assert set(tool.FAULTS) | set(tool.CONFIG_FAULTS) >= {
        "i_share_ignored_every_pair_computed",
        "ii_gates_a_softmax_over_all_72_logits", "iii_top_9",
        "iv_shared_expert_left_out", "v_attention_multiplier_1_64",
        "vi_reused_slot_keeps_its_state"}
    for name in [*tool.FAULTS, *tool.CONFIG_FAULTS]:
        if name == "vi_reused_slot_keeps_its_state":
            continue
        changed = run(name) if name != "v_attention_multiplier_1_64" \
            else np.asarray(family.model(dataclasses.replace(
                cfg, attention_multiplier=1 / 8)).module.apply(params,
                                                               tokens))
        assert np.abs(changed - sound).max() > 2e-3, name
    np.testing.assert_array_equal(run(None), sound)
    # float32 weights: one bfloat16 term is what the kernel is NOT handed
    # (a float32 matrix takes its rows whole): the control needs bf16
    assert "experts_in_one_bf16_term" in tool.CONTROLS
    # the stale state: a slot written twice holds the sum, not the second
    from ray_tpu.serve.llm_families import family_of

    fam = family_of(cfg, 64)
    state = fam.init_state(2, 5, 16)
    _, fresh, _ = fam.prefill(params, jnp.zeros((1, 16), jnp.int32)
                              .at[0, :9].set(tokens[0, :9]),
                              jnp.asarray([8]))
    slots, pages = jnp.asarray([1]), jnp.asarray([[1]])
    once = fam.write_prompt(state, fresh, slots, pages)
    with tool.planted("vi_reused_slot_keeps_its_state"):
        twice = fam.write_prompt(once, fresh, slots, pages)
    again = fam.write_prompt(once, fresh, slots, pages)
    np.testing.assert_array_equal(np.asarray(again["ssm"][0][1]),
                                  np.asarray(once["ssm"][0][1]))
    np.testing.assert_allclose(np.asarray(twice["ssm"][0][1][1]),
                               2 * np.asarray(once["ssm"][0][1][1]))


def test_a_checkout_without_the_routed_model_is_told_so_at_once(
        tmp_path, monkeypatch):
    """The parent commit with these benchmark files laid over it HAS
    `models/granite_hybrid.py` (the dense member): loading the family
    raises `BenchmarkError` (the command exits 1) before any cluster or
    replica is started, because the file has no routed experts; and where
    the file is not there at all."""
    monkeypatch.setattr(loader, "REPO_ROOT", str(tmp_path))
    with pytest.raises(loader.BenchmarkError, match="no routed experts"):
        loader.load_family("granite_moe_hybrid", _REPO, BENCH)
    models = tmp_path / "ray_tpu" / "models"
    models.mkdir(parents=True)
    (models / "granite_hybrid.py").write_text(
        '"""Granite-4.0-H decoder (`granitemoehybrid` with no routed '
        'experts)"""\nclass GraniteHybridConfig:\n    d_ff: int = 8192\n')
    with pytest.raises(loader.BenchmarkError, match="no routed experts"):
        loader.load_family("granite_moe_hybrid", _REPO, BENCH)
    (models / "granite_hybrid.py").write_text(
        open(os.path.join(_REPO, "ray_tpu", "models",
                          "granite_hybrid.py")).read())
    assert loader.load_family("granite_moe_hybrid", _REPO, BENCH).HEAD_DIM \
        == 128


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """An in-process cluster that offers `TPU: 1` (conftest's seam gives
    such a lease-holder the CPU) and a benchmark whose one cell is the tiny
    `granite_moe_hybrid` configuration under the tiny closed-loop mix,
    reporting what `granite4hs-serve-desk-closed` reports."""
    import ray_tpu
    from tests.conftest import _fast_config

    root = tmp_path_factory.mktemp("granite_moe_hybrid_rehearsal")
    bench = json.loads(json.dumps(BENCH))
    bench["paths"] = ["tests/benchmarks/granite_moe_hybrid"]
    bench["configs"] = [{
        "name": "tiny-granite-moe-hybrid", "source": "test",
        "reduced": ["num_local_experts"],
        "file": "tests/benchmarks/granite_moe_hybrid/configs/"
                "tiny-granite-moe-hybrid.json", "why": "test"}]
    bench["workloads"] = [{"name": "tiny.desk",
                           "config": "tiny-granite-moe-hybrid",
                           "traffic": "tiny-desk-closed", "chips": 1,
                           "why": "rehearsal"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.desk"] if CELL in m["workloads"] else []
    os.symlink(os.path.join(_REPO, "tests"), root / "tests")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    ray_tpu.init(num_cpus=4, resources={"TPU": 1}, config=_fast_config())
    yield str(root)
    ray_tpu.shutdown()


@pytest.mark.time_limit(360)
@pytest.mark.parametrize("trace", [False, True], ids=["timed", "traced"])
def test_rehearsal_desk_closed(rehearsal, trace):
    """The whole of a run but the look for a chip: replica up through
    serve.run, every bucket warmed, 6 clients on 4 slots for 2 s, drained,
    samples against the reference that holds the same share (its routing
    pass among the roundings), nothing compiled in the window."""
    lines = []
    cell = loader.load_cell("tiny.desk", rehearsal)
    assert cell.family.__file__ == os.path.join(
        _REPO, "benchmarks", "families", "granite_moe_hybrid.py")
    result = bench_run.run_cell(
        cell, 2 ** 31 + 13, 2.0, trace, time.monotonic(), platform="cpu",
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
        # nothing and leave their metrics out; the counters' readers read
        # the engine's spans, which are there)
        assert {"worker_ready_s", "batch_occupancy.closed",
                "experts_touched_share", "expert_pairs_held_share",
                "paged_live_share.closed"} <= set(result["metrics"])
        assert not {"gmh_decode_roofline", "gmh_prefill_mfu",
                    "gmh_gmm_roofline"} & set(result["metrics"])
        # four of eight experts held: about half of the pairs lie here,
        # and four rows of top-3 touch most of the four
        assert 25 <= result["metrics"]["expert_pairs_held_share"]["value"] \
            <= 75
        assert 25 <= result["metrics"]["experts_touched_share"]["value"] \
            <= 100
    else:
        assert set(result["metrics"]) == {"batch_tokens_per_s", "setup_s"}
        assert result["metrics"]["batch_tokens_per_s"]["value"] > 0
