"""The `mla_moe` family's part of the benchmark: its configuration file
against the catalog's keys, its cost functions by hand, its readers on
hand-made observations and span files, its reference's routing pass, and a
CPU rehearsal of `kimivl-serve-pages-closed` at tiny widths through the
harness's own closed-loop driver.  Every entry of `BENCHMARK.json` is
looked up by NAME and what the cell reports is compared as a superset, as
`test_lfm2_moe_cell.py` does, so that the next cell to be appended needs no
fixture to hide it from this module.
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

CELL = "kimivl-serve-pages-closed"
CONFIG = "kimi-vl-a3b-l7"
BENCH = loader.load_benchmark()
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = ("https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct/blob/main/"
          "config.json")
NEW_METRICS = {
    "mla_decode_roofline": ("model step", "device_trace"),
    "mla_paged_attn_roofline": ("kernels", "device_trace"),
    "mla_prefill_mfu": ("model step", "device_trace"),
    "latent_bytes_share": ("engine", "program_counter"),
    "mla_gmm_roofline": ("kernels", "device_trace")}
LISTED = {"batch_occupancy.closed", "prefill_device_ms.closed",
          "decode_step_ms.closed", "device_idle.closed",
          "queue_wait_ms.closed", "loop_host_ms.closed",
          "admit_host_ms.closed", "paged_live_share.closed",
          "experts_touched_share"}
SLOT = (10.0, 12.0)


def _named(entries, name):
    return next(e for e in entries if e["name"] == name)


@pytest.fixture(scope="module")
def cell():
    return loader.load_cell(CELL)


@pytest.fixture(scope="module")
def costs(cell):
    return cell.readers["mla_decode_roofline"].costs


# ---- the configuration, the mix and the cell, as the issue names them ------


def test_the_cell_is_as_named(cell):
    entry = _named(BENCH["workloads"], CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        (CONFIG, "pages-closed", 1)
    conf = _named(BENCH["configs"], CONFIG)
    assert conf["source"] == SOURCE == cell.config["source"]
    assert conf["reduced"] == ["num_hidden_layers"] == cell.config["reduced"]
    assert conf["file"] == "benchmarks/configs/kimi-vl-a3b-l7.json"
    t = cell.traffic
    assert (t["kind"], t["clients"], t["pool_requests"]) == \
        ("serve_closed", 64, 512)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 2048,
                                  "sigma": 0.7, "min": 512, "max": 8192}
    assert t["output_tokens"] == {"dist": "uniform", "min": 512, "max": 1024}
    assert t["sampling"] == "greedy" and t["shared_prefixes"] is False
    others = [json.load(open(os.path.join(_REPO, "benchmarks", "traffic", f)))
              for f in os.listdir(os.path.join(_REPO, "benchmarks",
                                               "traffic"))]
    assert [o["order_seed"] for o in others].count(t["order_seed"]) == 1
    engine = cell.config["serve"]["engine"]
    assert engine["max_batch"] == t["clients"] == 64
    # the longest prompt and answer and a page; the pool in whole pages
    assert engine["max_len"] == 8192 + 1024 + 64
    assert engine["kv_pool_tokens"] % engine["page_size"] == 0
    assert [m["name"] for m in cell.end_to_end] == ["batch_tokens_per_s",
                                                    "setup_s"]


def test_the_benchmark_holds_the_cell_by_name():
    reported = {m["name"] for m in BENCH["per_layer"]
                if "workloads" not in m or CELL in m["workloads"]}
    assert reported >= set(NEW_METRICS) | LISTED | {"worker_ready_s"}
    assert not {m for m in reported if m.startswith("moe_")}
    for name, (layer, source) in NEW_METRICS.items():
        m = _named(BENCH["per_layer"], name)
        assert (m["layer"], m["source"], m["unit"], m["better"], m["moves"],
                m["workloads"]) == (layer, source, "%", "higher",
                                    "batch_tokens_per_s", [CELL])
    assert CELL in _named(BENCH["end_to_end"],
                          "batch_tokens_per_s")["workloads"]
    # four-chip cells: none
    assert all(w["chips"] == 1 for w in BENCH["workloads"])


def test_the_file_holds_the_published_keys(cell):
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-VL-A3B-Instruct")
    assert row["source_url"] == SOURCE
    conf = cell.config
    differ = {k for k, v in row["config"].items() if conf.get(k) != v}
    assert differ == {"num_hidden_layers"} == set(conf["reduced"])
    assert conf["published"] == {"num_hidden_layers": 27}
    assert conf["num_hidden_layers"] == 7
    for key in ("rope_interleave", "selection_bias", "weights",
                "latent_norm"):
        assert key in conf["assumed"]
    d = conf["deployment"]
    assert d["chips_sharing_a_layer"] == 1 and d["chips_in_all"] == 4
    assert "absent" in d["tower"] and "BOTH" in d["what"]
    loader.check_configuration(conf, cell.family)


def test_a_file_of_the_family_is_held_to_its_own_rules(cell):
    def refused(match, **change):
        with pytest.raises((ValueError, loader.BenchmarkError), match=match):
            loader.check_configuration(dict(cell.config, **change),
                                       cell.family)

    refused("no query compression", q_lora_rank=1536)
    refused("rope_scaling null", rope_scaling={"type": "yarn", "factor": 4})
    refused("sigmoid", scoring_func="softmax")
    refused("group-limited", n_group=8)
    refused("keep no whole period", num_hidden_layers=4)
    refused("only the depth is cut",
            published={"num_hidden_layers": 27, "n_routed_experts": 64})
    refused("lets only", reduced=["num_hidden_layers", "hidden_size"],
            published={"num_hidden_layers": 27, "hidden_size": 4096})
    refused("is not under its published value",
            published={"num_hidden_layers": 7})
    refused("for every head", num_key_value_heads=8)


def test_costs_by_hand(cell, costs):
    sizes = cell.family.sizes(cell.config)
    # what a token caches: 512 + 64 values a layer, 1,152 bytes in bf16,
    # 8,064 over the seven layers
    assert costs.latent_values(sizes) == 576
    assert costs.latent_bytes_per_token(sizes) == 7 * 1152 == 8064
    mm = costs.matmul_params(sizes)
    assert mm["attention"] == 2048 * 16 * 192 + 2048 * 576 \
        + 512 * 16 * 256 + 16 * 128 * 2048
    assert mm["expert"] == 3 * 2048 * 1408
    assert mm["shared"] == 2 * mm["expert"]
    # a token's routed layer: the router, SIX experts and the two shared:
    # eight experts' worth
    routed = 2 * (mm["router"] + 8 * mm["expert"])
    assert costs.token_flops(sizes) == \
        2.0 * (7 * mm["attention"] + mm["dense"]) + 6 * routed
    # the parameters and bytes of the cut, as the program's own tree
    assert costs.parameters(sizes) == 4_263_151_488
    assert costs.weight_bytes(sizes) == 8_527_945_216
    assert costs.weight_bytes(sizes) / 1e9 == pytest.approx(
        cell.config["memory"]["weights_gb"], abs=1e-3)
    assert costs.expert_bytes(sizes) == 17_301_504
    # every weight that is no routed expert's, less the embedding's rows
    assert costs.other_bytes(sizes) == 8_527_945_216 \
        - 163840 * 2048 * 2 - 6 * 64 * 17_301_504
    # a decode step at 64 rows, 190,000 tokens resident, every expert
    # touched: bytes bind it (11.5 ms), a sixth of them latents
    flops, nbytes = costs.decode_step_cost(sizes, 64, 190_000, 6 * 64)
    assert nbytes == costs.other_bytes(sizes) + 384 * 17_301_504 \
        + 190_000 * 8064
    least, bound = kernel_costs.roofline_seconds(
        flops, nbytes, kernel_costs.peaks("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(11.46e-3, rel=5e-3)
    # the kernel: 16 heads x (576 + 512) x 2 operations and 1,152 bytes a
    # token: 30 operations a byte
    flops, nbytes = costs.latent_kernel_cost(sizes, 64, 190_000)
    assert flops == 190_000 * 16 * (576 + 512) * 2
    assert nbytes == 190_000 * 1152 + 64 * 16 * 4 * (576 + 512)
    assert 29 < flops / nbytes < 30.3
    # a prompt of 2,048: its tokens' products, the causal half of 16 heads
    # at 192 + 128, one row of the head
    assert costs.prefill_flops(sizes, 2048) == pytest.approx(
        2048 * costs.token_flops(sizes)
        + 7 * 2 * 16 * 320 * 2048 * 2049 / 2 + 2 * 2048 * 163840, rel=1e-12)
    # a step's custom calls: 7 latent calls, 12 grouped products
    order = costs.kernel_order(sizes)
    assert order[:5] == ["latent", "latent", "grouped", "grouped", "latent"]
    assert (order.count("latent"), order.count("grouped")) == (7, 12)
    # the grouped products of a step at 64 rows, every expert touched: 384
    # experts' matrices once and 2,304 pairs' rows in and out in float32;
    # bytes bind them (8.2 ms)
    flops, nbytes = costs.grouped_product_cost(sizes, 64 * 6 * 6, 6 * 64)
    assert flops == 2.0 * 2304 * 3 * 2048 * 1408
    assert nbytes == 384 * 17_301_504 + 2304 * 4 * (2048 + 2816 + 1408
                                                    + 2048)
    least, bound = kernel_costs.roofline_seconds(
        flops, nbytes, kernel_costs.peaks("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(8.21e-3, rel=5e-3)


# ---- the readers ------------------------------------------------------------


def _span(sid, name, t0_s, dur_ms, **attrs):
    return {"id": sid, "parent": None, "name": name,
            "t0_ns": int(t0_s * 1e9), "dur_ns": int(dur_ms * 1e6), "tid": 1,
            "thread": "llm-engine", "attrs": attrs}


@pytest.fixture
def spans(tmp_path, monkeypatch):
    """A session whose engine counted: two chunks of 8 steps in the traced
    slot, one before it in the window, one of another time."""
    monkeypatch.setenv("RAY_TPU_TEMP_DIR", str(tmp_path))
    logs = tmp_path / "session-a" / "logs"
    logs.mkdir(parents=True)
    chunk = dict(expert_slots=8 * 6 * 64, expert_rows_max=12)
    lines = [{"header": {"pid": 7, "label": "w", "time_s": 5000.0,
                         "mono_ns": int(8e9)}}] + [
        _span(1, "engine.decode.wait", 1.0, 150, experts_touched=3072,
              latent_tokens=9_000_000, **chunk),
        _span(2, "engine.decode.wait", 9.0, 150, experts_touched=3000,
              latent_tokens=1_000_000, **chunk),
        _span(3, "engine.decode.wait", 10.1, 150, experts_touched=3072,
              latent_tokens=1_500_000, **chunk),
        _span(4, "engine.decode.wait", 10.6, 150, experts_touched=3056,
              latent_tokens=1_540_000, **chunk)]
    (logs / "spans-w1.jsonl").write_text(
        "".join(json.dumps(x) + "\n" for x in lines))
    return tmp_path


def _obs(cell, **over):
    sizes = cell.family.sizes(cell.config)
    step = [0.4e6] + [0.4e6, 1.0e6, 0.5e6] * 6      # a step's 19 calls
    obs = {"sizes": sizes, "config": cell.config, "family": "mla_moe",
           "max_batch": 64, "window": (8.0, 14.0),
           "peaks": kernel_costs.peaks("TPU v5 lite"),
           # (t, slots taken, queued, streams decoding, their tokens)
           "samples": [(10.0 + i / 20, 64, 0, 63, 190_000)
                       for i in range(40)] + [(13.0, 2, 0, 2, 100)],
           "replica_spans": [
               {"prompt_len": 2048, "first": 10.9},
               {"prompt_len": 2048, "first": 11.4},
               {"prompt_len": 2048, "first": 12.4},     # past the slot
               {"prompt_len": 2048, "first": None}],
           "trace": {"window_mono_s": SLOT,
                     # two whole chunks and one cut short after 30 calls
                     "kernel_ns": {"decode_chunk_paged":
                                   (step * 8 * 3)[: 2 * 152 + 30]},
                     "program_ns": {"decode_chunk_paged": [144e6] * 2,
                                    "prefill_one": [0.3e9],
                                    "prefill_many": [0.2e9]}}}
    obs.update(over)
    return obs


def test_readers_on_hand_made_observations(cell, costs, spans):
    sizes = cell.family.sizes(cell.config)
    peak = kernel_costs.peaks("TPU v5 lite")
    roof, attn, mfu, share, gmm = (cell.readers[n] for n in NEW_METRICS)
    # the slot's two chunks touched (3072 + 3056) / 16 = 383 experts a step
    assert roof.touched_per_step(_obs(cell), 8) == 383.0
    least = kernel_costs.roofline_seconds(
        *costs.decode_step_cost(sizes, 63, 190_000, 383), peak)[0]
    # chunks of 8 steps in 144 ms: 18 ms a step
    assert roof.read(_obs(cell)) == pytest.approx(100 * least / 18e-3,
                                                  rel=1e-9)
    assert 63 < roof.read(_obs(cell)) < 64
    # the latent calls by their place in a step: 0.4 ms each, whatever the
    # grouped products around them take
    split = costs.split_kernel_calls(
        _obs(cell)["trace"]["kernel_ns"]["decode_chunk_paged"], sizes)
    assert set(split["latent"]) == {0.4e6}
    assert set(split["grouped"]) == {1.0e6, 0.5e6}
    assert len(split["latent"]) == 2 * 56 + 12
    kernel = kernel_costs.roofline_seconds(
        *costs.latent_kernel_cost(sizes, 64, 190_000), peak)[0]
    assert attn.read(_obs(cell)) == pytest.approx(100 * kernel / 0.4e-3,
                                                  rel=1e-9)
    assert 68 < attn.read(_obs(cell)) < 69
    # the grouped products by theirs: twelve a step, 1.0 + 0.5 ms a routed
    # layer, in two whole chunks and the 30 calls the trace still holds
    # of a third (a step and 6 of the next one's); their least time at the
    # slot's 63 rows of six experts in six routed layers, 383 experts touched
    assert len(split["grouped"]) == 2 * 96 + 18
    least = kernel_costs.roofline_seconds(*costs.grouped_product_cost(
        sizes, 63 * 6 * 6, 383), peak)[0]
    assert gmm.read(_obs(cell)) == pytest.approx(
        100 * least * (len(split["grouped"]) / 12)
        / (sum(split["grouped"]) / 1e9), rel=1e-9)
    assert 88 < gmm.read(_obs(cell)) < 92
    # two prompts of 2,048 in 0.5 s of prefill programs
    assert mfu.read(_obs(cell)) == pytest.approx(
        100 * 2 * costs.prefill_flops(sizes, 2048) / (0.5 * 197e12),
        rel=1e-9)
    assert 5 < mfu.read(_obs(cell)) < 6
    # the window's three chunks: latents over latents and weights
    latent = (1_000_000 + 1_500_000 + 1_540_000) * 8064
    weights = 3 * 8 * costs.other_bytes(sizes) \
        + (3000 + 3072 + 3056) * costs.expert_bytes(sizes)
    assert share.read(_obs(cell)) == pytest.approx(
        100 * latent / (latent + weights), rel=1e-12)
    assert 14 < share.read(_obs(cell)) < 18
    # the accepted reader of the experts' share runs unedited on this
    # family's counters
    assert cell.readers["experts_touched_share"].read(_obs(cell)) == \
        pytest.approx(100 * (3000 + 3072 + 3056) / (3 * 3072), rel=1e-12)
    # nothing to read: no trace, another family's cell, a trace without
    # the programs -- None, never an error
    for reader in (roof, attn, mfu, gmm):
        assert reader.read(_obs(cell, trace=None)) is None
        assert reader.read(_obs(cell, family="lfm2_moe")) is None
        assert reader.read(_obs(cell, trace={
            "window_mono_s": SLOT, "kernel_ns": {},
            "program_ns": {}})) is None
    assert share.read(_obs(cell, family="lfm2_moe")) is None
    for name in NEW_METRICS:
        m, reader = _named(BENCH["per_layer"], name), cell.readers[name]
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == \
            (m["layer"], m["unit"], m["moves"])


def test_readers_on_a_program_that_counts_nothing(cell, tmp_path,
                                                  monkeypatch):
    """The parent's program, or another family's: spans without the
    counters, or no span file at all.  Every new reader that reads a span
    returns None."""
    monkeypatch.setenv("RAY_TPU_TEMP_DIR", str(tmp_path))
    for name in ("mla_decode_roofline", "latent_bytes_share",
                 "mla_gmm_roofline"):
        assert cell.readers[name].read(_obs(cell)) is None
    logs = tmp_path / "session-b" / "logs"
    logs.mkdir(parents=True)
    lines = [{"header": {"pid": 7, "label": "w", "time_s": 5000.0,
                         "mono_ns": int(8e9)}},
             _span(3, "engine.decode.wait", 10.1, 90, active=4, steps=32,
                   pages_live=10, pages_table=100)]
    (logs / "spans-w1.jsonl").write_text(
        "".join(json.dumps(x) + "\n" for x in lines))
    for name in ("mla_decode_roofline", "latent_bytes_share",
                 "mla_gmm_roofline", "experts_touched_share"):
        assert cell.readers[name].read(_obs(cell)) is None


# ---- the reference's routing pass, and the rehearsal ------------------------


def _tiny_config():
    with open(os.path.join(_HERE, "mla_moe", "configs",
                           "tiny-mla-moe.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny_reference(cell):
    """(the reference, the tiny sizes, the family's weights with their
    deviations scaled to these widths, a sequence of 64 ids)."""
    import jax

    from tests.test_mla_moe import make

    jax.config.update("jax_platforms", "cpu")
    family = cell.family
    sizes = family.sizes(_tiny_config())
    params = make(family.program_config(sizes, attention="reference"))
    seq = np.random.default_rng(1).integers(1, 256, size=64).tolist()
    return family.reference, sizes, params, seq


def _stands_over(handed, sound, other):
    """`handed` is `sound` with the best token of `other` set over the
    sound best by what it lay under it, where the two differ."""
    best, moved = sound.max(-1), 0
    for k in range(len(sound)):
        token = int(other[k].argmax())
        expect = sound[k].copy()
        if token != sound[k].argmax():
            expect[token] = 2 * best[k] - sound[k, token]
            moved += 1
            assert handed[k].argmax() == token
        np.testing.assert_allclose(handed[k], expect, rtol=1e-6)
    return moved


def test_the_routing_pass_takes_the_served_passes_other_set(
        tiny_reference, monkeypatch):
    """At the family's tie no selection of these 128 is within it: the
    routing pass IS the served pass.  With a tie as wide as the scores
    themselves every selection is within it: the pass takes the fourth
    expert for the third at every token, and hands back the float32 logits
    with its own best token set over their best by what it lay under it."""
    ref, sizes, params, seq = tiny_reference
    sound = np.asarray(ref.logits(params, sizes, seq))
    rows = list(range(31, 64))
    assert (np.asarray(ref.logits(params, sizes, seq, rows,
                                  rounded=ref.ROUTING_PASS))
            == np.asarray(ref.logits(params, sizes, seq, rows,
                                     rounded=ref.SERVED))).all()
    monkeypatch.setattr(ref, "ROUTING_TIE", 10.0)
    ref.layer.clear_cache()
    try:
        handed = np.asarray(ref.logits(params, sizes, seq,
                                       rounded=ref.ROUTING_PASS))
        exchanged = np.asarray(ref.rounded_logits(
            params, sizes, seq, rounded=ref.ROUTING_PASS))
    finally:
        monkeypatch.undo()
        ref.layer.clear_cache()
    served = np.asarray(ref.rounded_logits(params, sizes, seq,
                                           rounded=ref.SERVED))
    # another expert at every token: not a rounding
    assert np.abs(exchanged - served).max() > 0.01
    assert _stands_over(handed, sound, exchanged) > 0


@pytest.mark.parametrize("level", [1, 2, 3])
def test_a_level_hands_back_its_best_token_standing(tiny_reference, level):
    """`logits(rounded=level)` is the float32 logits with the level's best
    token standing over them; the level's own logits (`rounded_logits`) lie
    a rounding away from the float32 ones, each level a little further."""
    ref, sizes, params, seq = tiny_reference
    assert ref.SERVED == 3 and ref.ROUTING_PASS == 4
    rows = list(range(31, 64))
    sound = np.asarray(ref.logits(params, sizes, seq, rows))
    own = np.asarray(ref.rounded_logits(params, sizes, seq, rows,
                                        rounded=level, prompt=32))
    assert 0 < np.abs(own - sound).max() < 0.05
    _stands_over(np.asarray(ref.logits(params, sizes, seq, rows,
                                       rounded=level)), sound, own)


def test_the_served_level_rounds_a_prompts_attention_only(tiny_reference):
    """One layer at the served level against the level before it: the
    positions of the prompt (the first 40 here) went through the flash
    kernel in bfloat16 and differ by a rounding; a generated position's
    attention is over the same cached rows in both and does not differ."""
    import jax.numpy as jnp

    ref, sizes, params, seq = tiny_reference
    p = params["params"]
    x = jnp.asarray(p["embed"]["embedding"])[jnp.asarray(seq)]
    args = dict(n_heads=4, kv_rank=32, d_nope=16, d_rope=8, theta=800000.0,
                eps=1e-5, top_k=3, scaling=2.446)
    before = np.asarray(ref.layer(x, p["layers_0"], rounded=2, **args)[0])
    served = np.asarray(ref.layer(x, p["layers_0"], rounded=ref.SERVED,
                                  prompt=40, **args)[0])
    whole = np.asarray(ref.layer(x, p["layers_0"], rounded=ref.SERVED,
                                 **args)[0])
    assert (served[40:] == before[40:]).all()
    moved = np.abs(served[:40] - before[:40]).max(-1)
    assert (moved > 0).all() and moved.max() < 5e-3    # (a stream of rms 1)
    # (no prompt given: every position is a prefill's)
    assert (whole[:40] == served[:40]).all()
    assert (np.abs(whole[40:] - before[40:]).max(-1) > 0).all()


def test_a_checkout_without_the_model_is_told_so_at_once(tmp_path,
                                                         monkeypatch):
    """The parent commit with these benchmark files laid over it: loading
    the family raises `BenchmarkError` (the command exits 1) before any
    cluster or replica is started."""
    monkeypatch.setattr(loader, "REPO_ROOT", str(tmp_path))
    with pytest.raises(loader.BenchmarkError,
                       match="no ray_tpu/models/mla_moe.py"):
        loader.load_family("mla_moe", _REPO, BENCH)


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """An in-process cluster that offers `TPU: 1` (conftest's seam gives
    such a lease-holder the CPU) and a benchmark whose one cell is the
    tiny `mla_moe` configuration under the tiny closed-loop mix, reporting
    what `kimivl-serve-pages-closed` reports."""
    import ray_tpu
    from tests.conftest import _fast_config

    root = tmp_path_factory.mktemp("mla_moe_rehearsal")
    bench = json.loads(json.dumps(BENCH))
    bench["paths"] = ["tests/benchmarks/mla_moe"]
    bench["configs"] = [{
        "name": "tiny-mla-moe", "source": "test", "reduced": [],
        "file": "tests/benchmarks/mla_moe/configs/tiny-mla-moe.json",
        "why": "test"}]
    bench["workloads"] = [{"name": "tiny.pages", "config": "tiny-mla-moe",
                           "traffic": "tiny-pages-closed", "chips": 1,
                           "why": "rehearsal"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.pages"] if CELL in m["workloads"] \
                else []
    os.symlink(os.path.join(_REPO, "tests"), root / "tests")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    ray_tpu.init(num_cpus=4, resources={"TPU": 1}, config=_fast_config())
    yield str(root)
    ray_tpu.shutdown()


@pytest.mark.time_limit(360)
@pytest.mark.parametrize("trace", [False, True], ids=["timed", "traced"])
def test_rehearsal_pages_closed(rehearsal, trace):
    """The whole of a run but the look for a chip: replica up through
    serve.run, every bucket warmed, 4 clients on 4 slots for 2 s, drained,
    samples against the reference (its routing pass among the roundings),
    nothing compiled in the window."""
    lines = []
    cell = loader.load_cell("tiny.pages", rehearsal)
    assert cell.family.__file__ == os.path.join(
        _REPO, "benchmarks", "families", "mla_moe.py")
    result = bench_run.run_cell(
        cell, 2 ** 31 + 45, 2.0, trace, time.monotonic(), platform="cpu",
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
                "experts_touched_share", "latent_bytes_share",
                "paged_live_share.closed"} <= set(result["metrics"])
        assert not {"mla_decode_roofline", "mla_prefill_mfu",
                    "mla_paged_attn_roofline"} & set(result["metrics"])
        assert 25 <= result["metrics"]["experts_touched_share"]["value"] \
            <= 100
        assert 0 < result["metrics"]["latent_bytes_share"]["value"] < 50
    else:
        assert set(result["metrics"]) == {"batch_tokens_per_s", "setup_s"}
        assert result["metrics"]["batch_tokens_per_s"]["value"] > 0
