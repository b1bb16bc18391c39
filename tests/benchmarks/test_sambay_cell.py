"""The `sambay` family's part of the benchmark: its configuration file,
its cost functions by hand, its readers on hand-made observations, its
reference against its model, and a CPU rehearsal of
`phi4flash-serve-reason-closed` at tiny widths through the harness's own
closed-loop driver.  New files only: nothing of `test_benchmark_harness.py`
is repeated or changed.
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

CELL = "phi4flash-serve-reason-closed"
BENCH = loader.load_benchmark()
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cell():
    return loader.load_cell(CELL)


@pytest.fixture(scope="module")
def prefill_mfu():
    """A reader no cell lists yet: the harness traces seconds 15.75-23.75
    of a window, and in this cell those are decode alone (the 32 requests
    that arrive together are prefilled by second 10, the first of them
    finishes after second 24: my chip runs, PR 28).  It is kept, with its
    cost function, for the `benchmark` PR that lets a mix place the slot."""
    return loader.sibling_reader(
        os.path.join(_REPO, "benchmarks", "layer_metrics", "x.py"),
        "prefill_mfu")


@pytest.fixture(scope="module")
def costs(prefill_mfu):
    return prefill_mfu.costs


# ---- the configuration, the mix and the cell, as the issue names them ------


def test_the_cell_is_as_named(cell):
    assert cell.chips == 1 and cell.family_name == "sambay"
    assert cell.config["reduced"] == [] and cell.config["source"] == \
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/" \
        "blob/main/config.json"
    mix = cell.traffic
    assert (mix["kind"], mix["clients"], mix["pool_requests"]) == \
        ("serve_closed", 32, 256)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 4096,
                                    "sigma": 0.8, "min": 512, "max": 16384}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 384,
                                    "max": 1024}
    assert mix["order_seed"] not in (20260927,)       # an order of its own
    assert cell.config["serve"]["engine"] == {
        "max_batch": 32, "max_len": 16384 + 1024 + 64, "page_size": 64,
        "decode_chunk": 8, "kv_pool_tokens": 303104}
    assert cell.config["serve"]["max_concurrency"] >= 40
    assert {m["name"] for m in cell.end_to_end} == {"batch_tokens_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "worker_ready_s", "batch_occupancy.closed", "decode_step_ms.closed",
        "device_idle.closed", "shared_kv_attn_roofline"}
    loader.check_configuration(cell.config, cell.family)


def test_the_file_holds_the_published_keys():
    """Every key of the catalog's copy of the published config.json, under
    the same name with the same value; the catalog is the guide's, outside
    the repository, so where it is not there the file's own numbers are
    held to the ones the issue gives."""
    with open(os.path.join(_REPO, "benchmarks", "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        conf = json.load(f)
    published = {"hidden_size": 2560, "intermediate_size": 10240,
                 "num_hidden_layers": 32, "num_attention_heads": 40,
                 "num_key_value_heads": 20, "sliding_window": 512,
                 "vocab_size": 200064, "tie_word_embeddings": True,
                 "layer_norm_eps": 1e-5, "mb_per_layer": 2}
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Phi-4-mini-flash-reasoning")
        published = row["config"]
        assert conf["source"] == row["source_url"]
    assert {k: conf[k] for k in published} == published
    assert "rope_theta" not in conf     # no position embedding of any kind


def test_a_file_of_the_family_is_held_to_its_own_rules(cell):
    for change, why in (({"num_attention_heads": 20}, "heads of 128"),
                        ({"num_hidden_layers": 30}, "multiple of four"),
                        ({"tie_word_embeddings": False}, "ties its head")):
        with pytest.raises(ValueError, match=why):
            cell.family.check_file(dict(cell.config, **change))
    assert cell.family.REDUCIBLE == set()
    with pytest.raises(loader.BenchmarkError, match="lets only"):
        loader.check_configuration(
            dict(cell.config, reduced=["num_hidden_layers"],
                 published={"num_hidden_layers": 64}), cell.family)


def test_the_parameter_count_from_the_file_is_3_85_billion(cell, costs):
    from ray_tpu.models.sambay import count_params

    sizes = cell.family.sizes(cell.config)
    counts = count_params(cell.family.program_config(sizes))
    assert counts["total"] == 3_852_457_984
    # 9 x 119.9 M + 9 x 98.3 M + 7 x 104.9 M + 7 x 91.8 M + 512.2 M
    assert sum(n * counts[k] for n, k in (
        (9, "mamba"), (9, "window"), (7, "gmu"), (7, "cross"))) \
        + counts["embedding"] + 2 * 2560 == counts["total"]
    assert counts["total"] * 2 / 1e9 == pytest.approx(7.705, abs=1e-3)
    # the cost file's multiplied parameters, by hand
    mlp = 3 * 2560 * 10240
    assert costs.matmul_params(sizes) == {
        "mamba": mlp + 2 * 2560 * 5120 + 5120 * 192 + 160 * 5120
        + 5120 * 2560,
        "window": mlp + 2560 * 80 * 64 + 2560 * 2560,
        "full": mlp + 2560 * 80 * 64 + 2560 * 2560,
        "gmu": mlp + 2 * 2560 * 5120,
        "cross": mlp + 2 * 2560 * 2560}
    kinds = costs.layer_kinds(sizes)
    assert kinds == [cell.family.program_config(sizes).kind(i)
                     for i in range(32)]
    params = costs.matmul_params(sizes)
    self_decoder = sum(params[k] for k in kinds[:18])
    assert self_decoder == 1_962_639_360            # "1.96 B of 3.34 B"
    assert sum(params[k] for k in kinds) == 3_338_895_360


# ---- cost functions by hand -------------------------------------------------


def test_costs_by_hand(cell, costs):
    sizes = cell.family.sizes(cell.config)
    assert costs.kv_bytes_per_token(sizes) == 5120
    assert costs.pool_readers(sizes) == 8
    assert costs.ring_bytes_per_step(sizes, 32) == 8 * 32 * 512 * 5120
    flops, nbytes = costs.shared_kv_decode_cost(sizes, 32, 182_400)
    assert flops == 2 * (64 + 128) * 40 * 182_400
    assert nbytes == 182_400 * 5120 + 32 * 40 * 192 * 2
    least, bound = kernel_costs.roofline_seconds(
        flops, nbytes, kernel_costs.peaks("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(1.1409e-3, rel=1e-3)
    # a prompt of 4096: 2 ops a multiplied parameter over the self-decoder,
    n = 4096
    want = 2 * 1_962_639_360 * n
    want += 9 * n * (2 * 4 * 5120 + 6 * 5120 * 16)          # conv and scan
    per_key = 2 * (64 + 128) * 40
    want += 8 * per_key * (512 * 513 // 2 + (n - 512) * 512)  # window layers
    want += per_key * (n * (n + 1) // 2)                      # the full one
    # the cross-decoder and the head once
    want += 2 * (7 * 104_857_600 + 7 * 91_750_400) + 7 * per_key * n
    want += 2 * 200064 * 2560
    assert costs.prefill_flops(sizes, n) == pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(16.47e12, rel=1e-3)
    # a prompt shorter than the window sees only the keys it has
    short = costs.prefill_flops(sizes, 100) - costs.prefill_flops(sizes, 99)
    assert short == pytest.approx(
        2 * 1_962_639_360 + 9 * (2 * 4 * 5120 + 6 * 5120 * 16)
        + 9 * per_key * 100 + 7 * per_key, rel=1e-9)
    # every layer at every position would be about 1.7 times the work
    every = 2 * 3_338_895_360 * n
    assert every / (2 * 1_962_639_360 * n) == pytest.approx(1.70, abs=0.01)


def _obs(cell, **over):
    sizes = cell.family.sizes(cell.config)
    obs = {"sizes": sizes, "config": cell.config, "family": "sambay",
           "max_batch": 32, "peaks": kernel_costs.peaks("TPU v5 lite"),
           "samples": [(10.0 + i / 20, 32, 0, 32, 182_400)
                       for i in range(40)],
           "replica_spans": [
               {"prompt_len": 4096, "first": 10.9},
               {"prompt_len": 4096, "first": 11.4},
               {"prompt_len": 4096, "first": 12.2},     # past the window
               {"prompt_len": 4096, "first": None}],
           "trace": {"window_mono_s": (10.0, 12.0),
                     "kernel_ns": {"decode_chunk_paged": [2.0e6] * 64},
                     "program_ns": {"prefill_one": [0.2e9],
                                    "prefill_many": [0.3e9]}}}
    obs.update(over)
    return obs


def test_readers_on_hand_made_observations(cell, prefill_mfu):
    roof = cell.readers["shared_kv_attn_roofline"]
    mfu = prefill_mfu
    # 64 calls of 2 ms against a least time of 1.1409 ms each
    assert roof.read(_obs(cell)) == pytest.approx(57.05, abs=0.05)
    # two prompts of 4096 (16.47 TFLOP each) in 0.5 s of prefill programs:
    # the window's edges move by the median run (0.25 s), so the request
    # at 12.2 s is in and none is before 10.25 s ... three requests
    got = mfu.read(_obs(cell))
    assert got == pytest.approx(
        100 * 3 * 16.47e12 / (0.5 * 197e12), rel=2e-3)
    # nothing to read: no trace, another family's cell, the parent's
    # program (no such programs in the trace) -- None, never an error
    for reader in (roof, mfu):
        assert reader.read(_obs(cell, trace=None)) is None
        assert reader.read(_obs(cell, family="dense_decoder")) is None
        assert reader.read(_obs(cell, trace={
            "window_mono_s": (10.0, 12.0), "kernel_ns": {},
            "program_ns": {}})) is None
    m = BENCH["per_layer"][-1]
    assert (roof.LAYER, roof.UNIT, roof.MOVES) == \
        (m["layer"], m["unit"], m["moves"])
    assert m["name"] == "shared_kv_attn_roofline" and m["workloads"] == [CELL]
    assert (mfu.LAYER, mfu.UNIT, mfu.MOVES) == \
        ("model step", "%", "batch_tokens_per_s")


# ---- the reference against the model, and the rehearsal ---------------------


def _tiny_config():
    with open(os.path.join(_HERE, "sambay", "configs",
                           "tiny-sambay.json")) as f:
        return json.load(f)


def test_reference_agrees_with_the_family_model_at_tiny_widths():
    """float32 on the CPU, seeded weights from the family's own `init`:
    the program's whole forward against the plain reference, 2e-5 (the
    logits lie within +-1.2; float32 reordering moves them by under 1e-6,
    K and V in bfloat16 by 5e-3: tests/test_models_sambay.py)."""
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    family = loader.load_family("sambay")
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
    rounded = np.asarray(family.reference.logits(
        params, sizes, tokens[0].tolist(), rounded=3))
    assert 1e-4 < np.abs(rounded - want).max() < 0.1
    # the loss the seam asks for, against the program's own
    loss = family.reference.mean_token_loss(
        params, sizes, [tokens[0, :-1].tolist()], [tokens[0, 1:].tolist()])
    assert loss == pytest.approx(float(family.loss(
        jnp.asarray(got[None, :-1]), jnp.asarray(tokens[:, 1:]))), abs=1e-4)
    assert math.isfinite(loss)
    with pytest.raises(ValueError, match="heads of 16"):
        family.check_file(_tiny_config())


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """An in-process cluster that offers `TPU: 1` (conftest's seam gives
    such a lease-holder the CPU) and a benchmark whose one cell is the
    tiny `sambay` configuration under the tiny closed-loop mix, reporting
    what `phi4flash-serve-reason-closed` reports."""
    import ray_tpu
    from tests.conftest import _fast_config

    root = tmp_path_factory.mktemp("sambay_rehearsal")
    bench = json.loads(json.dumps(BENCH))
    bench["paths"] = ["tests/benchmarks/sambay"]
    bench["configs"] = [{
        "name": "tiny-sambay", "source": "test", "reduced": [],
        "file": "tests/benchmarks/sambay/configs/tiny-sambay.json",
        "why": "test"}]
    bench["workloads"] = [{"name": "tiny.reason", "config": "tiny-sambay",
                           "traffic": "tiny-reason-closed", "chips": 1,
                           "why": "rehearsal"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.reason"] if CELL in m["workloads"] \
                else []
    os.symlink(os.path.join(_REPO, "tests"), root / "tests")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    ray_tpu.init(num_cpus=4, resources={"TPU": 1}, config=_fast_config())
    yield str(root)
    ray_tpu.shutdown()


@pytest.mark.time_limit(360)
@pytest.mark.parametrize("trace", [False, True], ids=["timed", "traced"])
def test_rehearsal_reason_closed(rehearsal, trace):
    """The whole of a run but the look for a chip: replica up through
    serve.run, every bucket warmed, 3 clients for 2 s, drained, four
    samples against the reference, nothing compiled in the window."""
    lines = []
    cell = loader.load_cell("tiny.reason", rehearsal)
    assert cell.family.__file__ == os.path.join(
        _REPO, "benchmarks", "families", "sambay.py")
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
    else:
        assert set(result["metrics"]) == {"batch_tokens_per_s", "setup_s"}
        assert result["metrics"]["batch_tokens_per_s"]["value"] > 0
