"""The `granite_hybrid` family's part of the benchmark: its configuration
file, its cost functions by hand, its readers on hand-made observations,
its reference against its model, and a CPU rehearsal of
`granite4h-serve-rows-closed` at tiny widths through the harness's own
closed-loop driver.  New files only: nothing of `test_benchmark_harness.py`
or `test_sambay_cell.py` is repeated or changed.
"""

import json
import math
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

CELL = "granite4h-serve-rows-closed"
BENCH = loader.load_benchmark()
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/" \
    "main/config.json"
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


@pytest.fixture(scope="module")
def cell():
    return loader.load_cell(CELL)


@pytest.fixture(scope="module")
def costs(cell):
    return cell.readers["ssm_decode_roofline"].costs


# ---- the configuration, the mix and the cell, as the issue names them ------


def test_the_cell_is_as_named(cell):
    assert cell.chips == 1 and cell.family_name == "granite_hybrid"
    assert cell.config["reduced"] == [] and cell.config["published"] == {}
    assert cell.config["source"] == SOURCE
    mix = cell.traffic
    assert (mix["kind"], mix["clients"], mix["pool_requests"]) == \
        ("serve_closed", 64, 1024)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.8, "min": 32, "max": 1024}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 128,
                                    "max": 512}
    assert mix["sampling"] == "greedy" and mix["shared_prefixes"] is False
    assert mix["order_seed"] == 20260930
    engine = cell.config["serve"]["engine"]
    assert engine["max_batch"] in (64, 48, 32)
    assert engine == {
        "max_batch": engine["max_batch"], "max_len": 1600, "page_size": 64,
        "decode_chunk": 8, "kv_pool_tokens": engine["max_batch"] * 1600}
    # the longest request fits: 1024 + 512 <= 1600
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= engine["max_len"]
    assert cell.config["serve"]["max_concurrency"] >= mix["clients"] + 8
    assert {m["name"] for m in cell.end_to_end} == {"batch_tokens_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "worker_ready_s", "batch_occupancy.closed",
        "prefill_device_ms.closed", "decode_step_ms.closed",
        "device_idle.closed", "ssm_decode_roofline", "ssm_prefill_mfu"}
    loader.check_configuration(cell.config, cell.family)
    # every † point of the issue, the initialiser and the sizing
    assert {"conv_activation", "dt_softplus", "gated_norm", "weights",
            "sampling", "max_batch", "kv_pool_tokens",
            "torch_dtype"} <= set(cell.config["assumed"])
    assert cell.config["precision"]["state"] == "float32"
    assert cell.config["memory"]["tried"].keys() == {"64", "48", "32"}


def test_the_benchmark_only_grew():
    """One configuration, one cell, two metrics, each at the end of its
    list; of what was there only the `workloads` lists of the metrics the
    cell reports changed, by the cell's name at their end."""
    assert BENCH["configs"][-1]["name"] == "granite-4.0-h-micro"
    assert BENCH["configs"][-1]["source"] == SOURCE
    assert BENCH["workloads"][-1] == {
        "name": CELL, "config": "granite-4.0-h-micro",
        "traffic": "rows-closed", "chips": 1,
        "why": BENCH["workloads"][-1]["why"]}
    assert [m["name"] for m in BENCH["per_layer"][-2:]] == [
        "ssm_decode_roofline", "ssm_prefill_mfu"]
    listed = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
              if CELL in m.get("workloads", [])]
    # (`prefill_device_ms.closed`: the first closed cell whose traced slot
    # holds prefills since docs-closed)
    assert listed == ["batch_tokens_per_s", "batch_occupancy.closed",
                      "prefill_device_ms.closed", "decode_step_ms.closed",
                      "device_idle.closed", "ssm_decode_roofline",
                      "ssm_prefill_mfu"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL
    # not a dense decoder's heads of 128: its kernel's yardstick is not ours
    paged = next(m for m in BENCH["per_layer"]
                 if m["name"] == "paged_attn_roofline")
    assert CELL not in paged["workloads"]


def test_the_file_holds_the_published_keys():
    """Every key of the catalog's copy of the published config.json, under
    the same name with the same value; the catalog is the guide's, outside
    the repository, so where it is not there the file's own numbers are
    held to the ones the issue gives."""
    with open(os.path.join(_REPO, "benchmarks", "configs",
                           "granite-4.0-h-micro.json")) as f:
        conf = json.load(f)
    published = {
        "hidden_size": 2048, "intermediate_size": 8192,
        "shared_intermediate_size": 8192, "num_hidden_layers": 40,
        "layer_types": PERIOD * 4, "num_attention_heads": 32,
        "num_key_value_heads": 8, "vocab_size": 100352,
        "mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_chunk_size": 256, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "residual_multiplier": 0.22,
        "logits_scaling": 8, "position_embedding_type": "nope",
        "num_local_experts": 0, "tie_word_embeddings": True,
        "rms_norm_eps": 1e-5}
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "granite-4.0-h-micro")
        assert {k: row["config"][k] for k in published} == published
        published = row["config"]
        assert conf["source"] == row["source_url"]
    assert {k: conf[k] for k in published} == published


def test_a_file_of_the_family_is_held_to_its_own_rules(cell):
    for change, why in (
            ({"num_attention_heads": 16}, "heads of 128"),
            ({"mamba_n_heads": 32}, "mamba_expand"),
            ({"tie_word_embeddings": False}, "ties its head"),
            ({"num_local_experts": 8}, "routed experts"),
            ({"mamba_n_groups": 8}, "one group"),
            ({"position_embedding_type": "rope"}, "no position term"),
            ({"layer_types": PERIOD * 3}, "each of num_hidden_layers")):
        with pytest.raises(ValueError, match=why):
            cell.family.check_file(dict(cell.config, **change))
    assert cell.family.REDUCIBLE == {"num_hidden_layers"}
    assert cell.family.layer_pattern(cell.config) == (0, 10)
    with pytest.raises(loader.BenchmarkError, match="lets only"):
        loader.check_configuration(
            dict(cell.config, reduced=["mamba_d_state"],
                 published={"mamba_d_state": 256}), cell.family)
    # a later cut keeps whole periods: 10 layers would pass, 8 would not
    cut = dict(cell.config, reduced=["num_hidden_layers"],
               published={"num_hidden_layers": 40})
    loader.check_configuration(
        dict(cut, num_hidden_layers=10, layer_types=PERIOD), cell.family)
    with pytest.raises(loader.BenchmarkError, match="whole period"):
        loader.check_configuration(
            dict(cut, num_hidden_layers=8, layer_types=PERIOD[:8]),
            cell.family)


def test_the_parameter_count_from_the_file_is_3_19_billion(cell, costs):
    from ray_tpu.models.granite_hybrid import count_params

    sizes = cell.family.sizes(cell.config)
    cfg = cell.family.program_config(sizes)
    counts = count_params(cfg)
    assert counts["total"] == 3_191_396_096 == costs.parameters(sizes)
    assert 36 * 76_182_976 + 4 * 60_821_504 + 205_520_896 + 2048 \
        == counts["total"]
    assert costs.weight_bytes(sizes) / 1e9 == pytest.approx(6.383, abs=1e-3)
    assert cfg.rope_theta is None and cfg.head_dim == 64
    assert cfg.attention_multiplier == 1 / 64 != 1 / math.sqrt(cfg.head_dim)
    # the cost file's multiplied parameters, by hand
    mlp = 3 * 2048 * 8192
    assert costs.matmul_params(sizes) == {
        "mamba": mlp + 2048 * 8512 + 4096 * 2048,
        "attention": mlp + 2 * 2048 * 2048 + 2 * 2048 * 512}
    assert costs.layers(sizes) == {"mamba": 36, "attention": 4}


# ---- cost functions by hand -------------------------------------------------

MULTIPLIED = 36 * (3 * 2048 * 8192 + 2048 * 8512 + 4096 * 2048) \
    + 4 * (3 * 2048 * 8192 + 2 * 2048 * 2048 + 2 * 2048 * 512)
RECURRENCE = 6 * 64 * 64 * 128          # a token and Mamba-2 layer
CONV = 2 * 4 * 4352


def test_costs_by_hand(cell, costs):
    sizes = cell.family.sizes(cell.config)
    assert costs.kv_bytes_per_token(sizes) == 8192
    assert costs.state_bytes_per_sequence(sizes) == \
        75_497_472 + 36 * 3 * 4352 * 2 == 76_437_504
    assert MULTIPLIED == 2_984_771_584
    # one step of 48 live slots holding 31,200 tokens (650 each)
    flops, nbytes = costs.decode_step_cost(sizes, 48, 31_200)
    assert flops == 48 * (2 * (MULTIPLIED + 100352 * 2048)
                          + 36 * (RECURRENCE + CONV)) \
        + 4 * 4 * 32 * 64 * 31_200
    assert nbytes == 2 * 3_191_396_096 + 2 * 48 * 76_437_504 \
        + 31_200 * 8192
    assert nbytes / 1e9 == pytest.approx(13.98, abs=0.01)
    least, bound = kernel_costs.roofline_seconds(
        flops, nbytes, kernel_costs.peaks("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(17.07e-3, rel=1e-3)
    # state is over 40% of a step's bytes at 48 slots, over half at 64
    assert 2 * 48 * 76_437_504 / nbytes > 0.5
    # no slot live, nothing resident: the weights alone
    assert costs.decode_step_cost(sizes, 0, 0) == (0.0, 2 * 3_191_396_096)
    # a prompt of 256
    n = 256
    want = 2 * MULTIPLIED * n + 4 * 4 * 32 * 64 * n * (n + 1) / 2 \
        + 36 * n * (RECURRENCE + CONV) + 2 * 100352 * 2048
    assert costs.prefill_flops(sizes, n) == pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(1.559e12, rel=1e-3)
    # one more token: its projections, its keys, its step of the recurrence
    more = costs.prefill_flops(sizes, 101) - costs.prefill_flops(sizes, 100)
    assert more == pytest.approx(
        2 * MULTIPLIED + 4 * 4 * 32 * 64 * 101 + 36 * (RECURRENCE + CONV),
        rel=1e-9)
    # the recurrence is about 2% of a Mamba-2 layer's arithmetic a token
    per_layer = 2 * (3 * 2048 * 8192 + 2048 * 8512 + 4096 * 2048)
    assert RECURRENCE / per_layer == pytest.approx(0.021, abs=0.001)


def _obs(cell, **over):
    sizes = cell.family.sizes(cell.config)
    obs = {"sizes": sizes, "config": cell.config,
           "family": "granite_hybrid", "max_batch": 48,
           "peaks": kernel_costs.peaks("TPU v5 lite"),
           # (t, slots taken, queued, streams decoding, their tokens): two
           # slots await their prefill, and the reader counts them out
           "samples": [(10.0 + i / 20, 50, 16, 48, 31_200)
                       for i in range(40)] + [(13.0, 2, 0, 2, 100)],
           "replica_spans": [
               {"prompt_len": 256, "first": 10.9},
               {"prompt_len": 256, "first": 11.4},
               {"prompt_len": 256, "first": 12.2},      # past the slot
               {"prompt_len": 256, "first": None}],
           "trace": {"window_mono_s": (10.0, 12.0),
                     "kernel_ns": {},
                     "program_ns": {"decode_chunk_paged": [200e6] * 9,
                                    "prefill_one": [0.04e9],
                                    "prefill_many": [0.06e9]}}}
    obs.update(over)
    return obs


def test_readers_on_hand_made_observations(cell):
    roof = cell.readers["ssm_decode_roofline"]
    mfu = cell.readers["ssm_prefill_mfu"]
    # chunks of 8 steps in 200 ms: 25 ms a step against a least 17.07 ms
    assert roof.read(_obs(cell)) == pytest.approx(68.3, abs=0.05)
    # three prompts of 256 (1.559 TFLOP each) in 0.1 s of prefill
    # programs: the slot's edges move by the median run (0.05 s), so the
    # request at 12.2 s is out and none is before 10.05 s: two requests
    assert mfu.read(_obs(cell)) == pytest.approx(
        100 * 2 * 1.559e12 / (0.1 * 197e12), rel=2e-3)
    # nothing to read: no trace, another family's cell, the parent's
    # program (no such programs in the trace) -- None, never an error
    for reader in (roof, mfu):
        assert reader.read(_obs(cell, trace=None)) is None
        assert reader.read(_obs(cell, family="sambay")) is None
        assert reader.read(_obs(cell, family="dense_decoder")) is None
        assert reader.read(_obs(cell, trace={
            "window_mono_s": (10.0, 12.0), "kernel_ns": {},
            "program_ns": {}})) is None
    for reader, m in zip((roof, mfu), BENCH["per_layer"][-2:]):
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == \
            (m["layer"], m["unit"], m["moves"])
        assert m["workloads"] == [CELL] and m["source"] == "device_trace"


# ---- the reference against the model, and the rehearsal ---------------------


def _tiny_config():
    with open(os.path.join(_HERE, "granite_hybrid", "configs",
                           "tiny-granite.json")) as f:
        return json.load(f)


def test_reference_agrees_with_the_family_model_at_tiny_widths():
    """float32 on the CPU, seeded weights from the family's own `init`:
    the program's whole forward against the plain reference, 2e-5 (at a
    width of 64 the family's initialiser gives logits within +-0.8;
    float32 reordering moves them by under 1e-6)."""
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    family = loader.load_family("granite_hybrid")
    sizes = family.sizes(_tiny_config())
    cfg = family.program_config(sizes, attention="reference")
    model = family.model(cfg)
    params = model.init(jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))
    tokens = np.random.default_rng(3).integers(1, 256, size=(1, 41))
    got = np.asarray(model.apply(params, jnp.asarray(tokens)))[0]
    want = np.asarray(family.reference.logits(params, sizes,
                                              tokens[0].tolist()))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    rows = [5, 40]
    np.testing.assert_allclose(
        np.asarray(family.reference.logits(params, sizes,
                                           tokens[0].tolist(), rows)),
        want[rows], atol=1e-6)
    assert len(family.reference.ROUNDINGS) == 4
    # each level rounds more; none of them touches the state's type
    off = [np.abs(np.asarray(family.reference.logits(
        params, sizes, tokens[0].tolist(), rounded=level)) - want).max()
        for level in (1, 2, 3)]
    assert 1e-6 < off[0] < off[2] < 0.1
    assert all("float32" in r for r in family.reference.ROUNDINGS[:2])
    # the loss the seam asks for, against the program's own
    loss = family.reference.mean_token_loss(
        params, sizes, [tokens[0, :-1].tolist()], [tokens[0, 1:].tolist()])
    assert loss == pytest.approx(float(family.loss(
        jnp.asarray(got[None, :-1]), jnp.asarray(tokens[:, 1:]))), abs=1e-4)
    assert math.isfinite(loss)
    with pytest.raises(ValueError, match="heads of 16"):
        family.check_file(_tiny_config())


def test_a_checkout_without_the_model_is_told_so_at_once(tmp_path,
                                                         monkeypatch):
    """The parent commit with these benchmark files laid over it: loading
    the family raises `BenchmarkError` (the command exits 1) before any
    cluster or replica is started."""
    monkeypatch.setattr(loader, "REPO_ROOT", str(tmp_path))
    with pytest.raises(loader.BenchmarkError,
                       match="no ray_tpu/models/granite_hybrid.py"):
        loader.load_family("granite_hybrid", _REPO, BENCH)


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """An in-process cluster that offers `TPU: 1` (conftest's seam gives
    such a lease-holder the CPU) and a benchmark whose one cell is the
    tiny `granite_hybrid` configuration under the tiny closed-loop mix,
    reporting what `granite4h-serve-rows-closed` reports."""
    import ray_tpu
    from tests.conftest import _fast_config

    root = tmp_path_factory.mktemp("granite_rehearsal")
    bench = json.loads(json.dumps(BENCH))
    bench["paths"] = ["tests/benchmarks/granite_hybrid"]
    bench["configs"] = [{
        "name": "tiny-granite", "source": "test", "reduced": [],
        "file": "tests/benchmarks/granite_hybrid/configs/tiny-granite.json",
        "why": "test"}]
    bench["workloads"] = [{"name": "tiny.rows", "config": "tiny-granite",
                           "traffic": "tiny-rows-closed", "chips": 1,
                           "why": "rehearsal"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.rows"] if CELL in m["workloads"] \
                else []
    os.symlink(os.path.join(_REPO, "tests"), root / "tests")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    ray_tpu.init(num_cpus=4, resources={"TPU": 1}, config=_fast_config())
    yield str(root)
    ray_tpu.shutdown()


@pytest.mark.time_limit(360)
@pytest.mark.parametrize("trace", [False, True], ids=["timed", "traced"])
def test_rehearsal_rows_closed(rehearsal, trace):
    """The whole of a run but the look for a chip: replica up through
    serve.run, every bucket warmed, 6 clients over 4 slots for 2 s,
    drained, samples against the reference, nothing compiled in the
    window."""
    lines = []
    cell = loader.load_cell("tiny.rows", rehearsal)
    assert cell.family.__file__ == os.path.join(
        _REPO, "benchmarks", "families", "granite_hybrid.py")
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
        # nothing and leave their metrics out)
        assert {"worker_ready_s", "batch_occupancy.closed"} <= \
            set(result["metrics"])
        assert not {"ssm_decode_roofline", "ssm_prefill_mfu"} & \
            set(result["metrics"])
        assert result["metrics"]["batch_occupancy.closed"]["value"] > 0
    else:
        assert set(result["metrics"]) == {"batch_tokens_per_s", "setup_s"}
        assert result["metrics"]["batch_tokens_per_s"]["value"] > 0
