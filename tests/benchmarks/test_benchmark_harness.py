"""The benchmark's own harness (`benchmarks/`): loader, traffic, span and
trace arithmetic, operation counts, the plain reference, the contract's
naming rules, and one CPU rehearsal of the drivers at TINY widths.

Nothing here describes a topology or touches a backend at import.
"""

import json
import math
import os
import re
import shutil
import subprocess
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
from benchmarks.harness import (kernel_costs, loader, stats,  # noqa: E402
                                trace_reduce, traffic)
from benchmarks.families.dense_decoder import (  # noqa: E402
    program_config as llama_config, sizes as model_sizes)

BENCH = loader.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def _config(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    with open(os.path.join(_REPO, entry["file"])) as f:
        return json.load(f)


def _traffic(name):
    with open(os.path.join(_REPO, "benchmarks", "traffic", name + ".json")) as f:
        return json.load(f)


# ---- loader: files found by name ------------------------------------------


@pytest.fixture
def made_up_root(tmp_path):
    """A benchmark of its own: one made-up cell, its configuration, mix and
    per-layer metric as new files; no file of `benchmarks/` is edited."""
    cells = tmp_path / "extra"
    for sub in ("configs", "traffic", "layer_metrics"):
        (cells / sub).mkdir(parents=True)
    with open(os.path.join(_HERE, "cells", "configs", "tiny.json")) as f:
        (cells / "configs" / "made-up.json").write_text(f.read())
    with open(os.path.join(_HERE, "cells", "traffic", "tiny-open.json")) as f:
        (cells / "traffic" / "made-up-mix.json").write_text(f.read())
    (cells / "layer_metrics" / "requests_seen.py").write_text(
        "LAYER = 'serve'\nUNIT = 'requests'\nMOVES = 'tpot_p90_ms'\n\n\n"
        "def read(obs):\n    return len(obs.get('client_spans', [])) or None\n")
    bench = {
        "command": BENCH["command"], "paths": ["extra"], "run_seconds": 2,
        "configs": [{"name": "made-up", "source": "test",
                     "file": "extra/configs/made-up.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "made-up.cell", "config": "made-up",
                       "traffic": "made-up-mix", "chips": 1, "why": "test"}],
        "end_to_end": [dict(m) for m in BENCH["end_to_end"]
                       if m["name"] in ("tpot_p90_ms", "setup_s")],
        "per_layer": [{"name": "requests_seen", "unit": "requests",
                       "better": "higher", "source": "program_counter",
                       "layer": "serve", "moves": "tpot_p90_ms"},
                      {"name": "engine_ttft_ms", "unit": "ms",
                       "better": "lower", "source": "program_span",
                       "layer": "engine", "moves": "tpot_p90_ms",
                       "workloads": ["another.cell"]}]}
    for m in bench["end_to_end"]:
        m.pop("workloads", None)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def test_loader_finds_a_made_up_cell_from_files_alone(made_up_root):
    cell = loader.load_cell("made-up.cell", str(made_up_root))
    assert cell.config["hidden_size"] == 128
    assert cell.traffic["kind"] == "serve_open"
    # The driver is the harness's own, found by the mix's kind.
    assert cell.driver.__file__.endswith("drivers/serve_open.py")
    # A metric that lists other cells is not this cell's.
    assert list(cell.readers) == ["requests_seen"]
    got = loader.read_layer_metrics(cell, {"client_spans": [1, 2, 3]})
    assert got == {"requests_seen": {"value": 3.0, "unit": "requests"}}
    # A reader that finds nothing returns nothing: the metric is left out.
    assert loader.read_layer_metrics(cell, {}) == {}


@pytest.mark.parametrize("missing", ["extra/configs/made-up.json",
                                     "extra/traffic/made-up-mix.json",
                                     "extra/layer_metrics/requests_seen.py"])
def test_loader_rejects_a_missing_file(made_up_root, missing):
    os.remove(made_up_root / missing)
    with pytest.raises(loader.BenchmarkError, match="no "):
        loader.load_cell("made-up.cell", str(made_up_root))


def test_loader_rejects_an_unknown_cell_and_kind(made_up_root):
    with pytest.raises(loader.BenchmarkError, match="no workload"):
        loader.load_cell("nothing", str(made_up_root))
    mix = made_up_root / "extra" / "traffic" / "made-up-mix.json"
    mix.write_text(json.dumps(dict(json.loads(mix.read_text()),
                                   kind="no_such_kind")))
    with pytest.raises(loader.BenchmarkError, match="no_such_kind.py"):
        loader.load_cell("made-up.cell", str(made_up_root))


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_of_the_benchmark_loads(name):
    cell = loader.load_cell(name)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        reader = cell.readers[m["name"]]
        # The reader's own declaration agrees with BENCHMARK.json, and the
        # end-to-end metric it moves is reported in this cell.
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == \
            (m["layer"], m["unit"], m["moves"])
        assert m["moves"] in {e["name"] for e in cell.end_to_end}


# ---- the contract's rules on names -----------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_names_units_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(CELLS)
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in BENCH["end_to_end"])


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_files_say_where_they_come_from(entry):
    conf = _config(entry["name"])
    assert conf["source"] == entry["source"]
    assert conf["reduced"] == entry["reduced"]
    # The keys a file must have, what may be cut and how far (named with
    # its published value; no width is), the compile's memory report, and
    # what the file's family asks of it (`dense_decoder`: heads of 128
    # that make up the hidden size, only the depth cut).
    family = loader.load_family(conf.get("family", loader.DEFAULT_FAMILY))
    loader.check_configuration(conf, family)


def _expert_family(**kw):
    """A family with experts and a layer pattern, as a later PR's might be."""
    import types

    return types.SimpleNamespace(**{
        "REDUCIBLE": {"num_hidden_layers", "experts_held", "vocab_size"},
        "EXPERTS_KEY": "experts_held",
        "layer_pattern": lambda conf: (conf["leading_dense"],
                                       conf["period"]),
        "check_file": lambda conf: None, **kw})


def _expert_file(**changes):
    conf = {"source": "test", "assumed": {}, "memory": {"temporaries": 1},
            "deployment": {"chips_sharing_a_layer": 8},
            "hidden_size": 2048, "num_hidden_layers": 7, "leading_dense": 1,
            "period": 3, "experts_held": 8, "vocab_size": 16384,
            "reduced": ["num_hidden_layers", "experts_held", "vocab_size"],
            "published": {"num_hidden_layers": 27, "experts_held": 64,
                          "vocab_size": 131072}}
    conf.update(changes)
    return conf


def test_a_cut_configuration_that_keeps_to_the_guide_passes():
    loader.check_configuration(_expert_file(), _expert_family())


@pytest.mark.parametrize("changes, why", [
    ({"reduced": ["hidden_size"], "published": {"hidden_size": 4096}},
     "lets only"),
    ({"published": {"num_hidden_layers": 7, "experts_held": 64,
                    "vocab_size": 131072}}, "not under its published"),
    ({"num_hidden_layers": 4}, "no whole period"),
    ({"num_hidden_layers": 6, "period": 6}, "no whole period"),
    ({"experts_held": 4}, "the floor is 8"),
    ({"vocab_size": 8192}, "under an eighth"),
    ({"deployment": "one chip of a fleet"}, "chips_sharing_a_layer"),
    ({"memory": {}}, "memory report"),
], ids=["a-width-cut", "not-under-published", "under-four-layers",
        "no-whole-period", "under-eight-experts", "thin-vocabulary",
        "no-deployment-share", "no-memory-report"])
def test_a_configuration_that_breaks_a_rule_is_refused(changes, why):
    with pytest.raises(loader.BenchmarkError, match=why):
        loader.check_configuration(_expert_file(**changes),
                                   _expert_family())


def test_a_file_is_held_to_its_familys_own_rule():
    conf = dict(_config("mistral-7b-v0.3-l16"), head_dim=64,
                num_attention_heads=64)
    with pytest.raises(ValueError, match="heads of 64"):
        loader.check_configuration(conf, loader.load_family("dense_decoder"))


# ---- traffic ----------------------------------------------------------------


def _requests(mix, seed, seconds=45.0, vocab=32768):
    return traffic.serve_requests(mix, seed, vocab, seconds)


def test_serve_traffic_is_deterministic_in_the_seed():
    mix = _traffic("chat-open")
    big = 2 ** 31 + 12345          # seeds pass 32 signed bits
    a, b = _requests(mix, big), _requests(mix, big)
    assert [(r.due_s, r.prompt_tokens, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt_tokens, r.max_new_tokens) for r in b]
    c = _requests(mix, big + 1)
    assert [r.prompt_tokens for r in a] != [r.prompt_tokens for r in c]
    # Another seed: other token values, the very same schedule (the order
    # belongs to the mix; see harness/traffic.py for what a rotation cost).
    assert [(r.due_s, len(r.prompt_tokens), r.max_new_tokens) for r in a] \
        == [(r.due_s, len(r.prompt_tokens), r.max_new_tokens) for r in c]
    other = dict(mix, order_seed=mix["order_seed"] + 1)
    d = _requests(other, big)
    assert [len(r.prompt_tokens) for r in a] != \
        [len(r.prompt_tokens) for r in d]
    assert sorted(len(r.prompt_tokens) for r in a) == \
        sorted(len(r.prompt_tokens) for r in d)


def test_serve_traffic_matches_its_file():
    mix = _traffic("chat-open")
    reqs = _requests(mix, 3, seconds=200.0)
    assert len(reqs) == round(mix["rate_rps"] * 200)
    due = np.array([r.due_s for r in reqs])
    assert (np.diff(due) >= 0).all() and due[0] == 0 and due[-1] < 200.0
    gaps = np.diff(due)
    # Poisson arrivals: exponential gaps, mean 1/rate and CV about 1.
    assert abs(gaps.mean() - 1 / mix["rate_rps"]) < 0.03 / mix["rate_rps"]
    assert 0.85 < gaps.std() / gaps.mean() < 1.1
    p = np.array([len(r.prompt_tokens) for r in reqs])
    o = np.array([r.max_new_tokens for r in reqs])
    for arr, spec in ((p, mix["prompt_tokens"]), (o, mix["output_tokens"])):
        assert arr.min() >= spec["min"] and arr.max() <= spec["max"]
        assert abs(np.median(arr) - spec["median"]) <= 0.03 * spec["median"]
        inner = arr[(arr > spec["min"]) & (arr < spec["max"])]
        assert abs(np.log(inner).std() - spec["sigma"]) < 0.25 * spec["sigma"]
    assert all(1 <= t < 32768 for r in reqs[:20] for t in r.prompt_tokens)


def test_closed_loop_pool_and_uniform_lengths():
    mix = _traffic("docs-closed")
    reqs = _requests(mix, 9)
    assert len(reqs) == mix["pool_requests"] >= 30 * mix["clients"]
    assert all(r.due_s == 0 for r in reqs)
    o = np.array([r.max_new_tokens for r in reqs])
    assert o.min() >= 16 and o.max() <= 64 and abs(o.mean() - 40) < 1
    p = np.array([len(r.prompt_tokens) for r in reqs])
    assert p.min() >= 512 and p.max() <= 2048
    assert abs(np.median(p) - 1024) < 30
    assert traffic.buckets_of(mix, 64, 2304) == [512, 1024, 2048]
    assert traffic.buckets_of(_traffic("chat-open"), 64, 2304) == \
        [64, 128, 256, 512, 1024]


def test_document_packer_packs_whole_rows_from_the_seed():
    mix = _traffic("packed2k")
    a = traffic.DocumentPacker(mix, 5, 92544)
    b = traffic.DocumentPacker(mix, 5, 92544)
    x, y = a.batch(4), b.batch(4)
    assert x.shape == (4, 2049) and x.dtype == np.int32
    assert (x == y).all() and (a.batch(4) == b.batch(4)).all()
    assert not (x == traffic.DocumentPacker(mix, 6, 92544).batch(4)).all()
    many = np.concatenate([a.batch(4).ravel() for _ in range(50)])
    docs = np.diff(np.flatnonzero(many == mix["eos_token"]))
    spec = mix["document_tokens"]
    assert docs.min() >= spec["min"] and docs.max() <= spec["max"] + 1
    assert abs(np.median(docs) - spec["median"]) < 0.15 * spec["median"]
    assert many.max() < 92544


# ---- span arithmetic ----------------------------------------------------------


def test_percentile_by_hand():
    xs = [10, 20, 30, 40, 50]
    assert stats.percentile(xs, 0) == 10 and stats.percentile(xs, 100) == 50
    assert stats.median(xs) == 30
    assert stats.percentile(xs, 90) == pytest.approx(46.0)
    assert stats.percentile(list(range(1, 102)), 90) == pytest.approx(91.0)
    values = np.random.default_rng(0).normal(size=257).tolist()
    for q in (50, 90, 95):
        assert stats.percentile(values, q) == pytest.approx(
            float(np.percentile(values, q)))
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_ttft_counts_from_the_due_time_and_tpot_over_the_request():
    # Due at 10.0, sent 0.2 s late, first token at 10.5: the user waited
    # 500 ms, whatever the generator did.
    span = {"due": 10.0, "sent": 10.2, "first": 10.5, "last": 12.5,
            "tokens": 41, "token_times": [10.5] + [11.0] * 20 + [12.5] * 20}
    assert stats.ttft_ms(span) == pytest.approx(500.0)
    # 40 gaps in 2.0 s: 50 ms a token, though single gaps are 0 or 1.5 s.
    assert stats.tpot_ms(span) == pytest.approx(50.0)
    assert stats.tpot_ms(dict(span, tokens=1)) is None
    assert stats.tokens_in_window(span, 10.0, 11.0) == 21
    assert stats.tokens_in_window(span, 11.5, 13.0) == 20


class _FakeHandle:
    """A Serve handle that streams `max_new_tokens` tokens, slowly."""

    def options(self, **_kw):
        return self

    def remote(self, payload):
        def stream():
            for i in range(payload["max_new_tokens"]):
                time.sleep(0.005)
                yield i
        return stream()


@pytest.mark.parametrize("kind", ["serve_open", "serve_closed"])
def test_schedules_on_a_fake_handle(kind):
    from benchmarks.harness import serve_common

    mix = json.load(open(os.path.join(
        _HERE, "cells", "traffic",
        "tiny-open.json" if kind == "serve_open" else "tiny-closed.json")))
    if kind == "serve_open":
        mix["rate_rps"] = 40.0
    reqs = traffic.serve_requests(mix, 1, 256, 0.5)
    bench = dict(BENCH, paths=["benchmarks"])
    driver = loader._load_module(loader.find_file(
        bench, _REPO, "drivers", kind + ".py"), "_t_" + kind)
    ctx = serve_common.Context(_FakeHandle(), reqs, time.monotonic(), 0.5,
                               mix)
    threads = driver._schedule(ctx)
    for t in threads:
        t.join(5.0)
    assert not any(t.is_alive() for t in threads)
    done = [s for s in ctx.spans if s["tokens"] == s["want"]]
    assert done and len(done) == len(ctx.spans)
    if kind == "serve_open":
        assert len(ctx.spans) == len(reqs) == 20
        # Sent at the due times (a starved generator would show here).
        late = [s["sent"] - s["due"] for s in ctx.spans]
        assert max(late) < 0.05 and min(late) >= 0
    else:
        # Each client sends its next only when its last came back.
        assert len(ctx.spans) < len(reqs)
        by_client = {}
        for s in sorted(ctx.spans, key=lambda s: s["sent"]):
            by_client.setdefault(s["rid"] % mix["clients"], []).append(s)
        assert len(by_client) == mix["clients"]
        for spans in by_client.values():
            for a, b in zip(spans, spans[1:]):
                assert b["sent"] >= a["done"]


# ---- trace reduction ------------------------------------------------------------


def _synthetic():
    us = 1e3
    ops = [["while.9", 0, 150 * us],      # a scan: it contains the next two
           ["fusion.1", 0, 100 * us],
           ["tpu_custom_call:attn.2", 50 * us, 100 * us],
           ["fusion.3", 400 * us, 100 * us],
           ["tpu_custom_call:attn.4", 600 * us, 50 * us],
           ["copy.5", 900 * us, 10 * us]]
    modules = [["jit_decode_chunk_paged(7)", 0, 200 * us],
               ["jit_prefill_many(9)", 400 * us, 250 * us],
               ["jit_decode_chunk_paged(7)", 900 * us, 10 * us]]
    return {"devices": {"/device:TPU:0": {"modules": modules, "ops": ops}},
            "sync_ns": None}


def test_trace_reduce_on_hand_made_events():
    r = trace_reduce.reduce_events(_synthetic(), (0.0, 1e6))
    # Busy: [0,150] + [400,500] + [600,650] + [900,910] us of 1000 us.
    assert r["busy_s"] == pytest.approx(310e-6)
    assert r["window_s"] == pytest.approx(1e-3)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.69)
    assert r["program_ns"] == {"decode_chunk_paged": [200e3, 10e3],
                               "prefill_many": [250e3]}
    assert r["kernel_ns"] == {"decode_chunk_paged": [100e3],
                              "prefill_many": [50e3]}
    # Costliest operations, by family; the wrapping `while` is not one.
    assert r["device_ops"] == [["fusion", pytest.approx(200e-6)],
                               ["tpu_custom_call:attn", pytest.approx(150e-6)],
                               ["copy", pytest.approx(10e-6)]]
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(690e-6)
    # Gaps of 250, 100, 250 and 90 us are named; none is under 50 us here.
    named = trace_reduce.reduce_events(
        _synthetic(), (0.0, 1e6),
        lambda a, b: "long" if b - a > 200e3 else "short")
    assert dict(named["idle_gaps"]) == {"long": pytest.approx(500e-6),
                                        "short": pytest.approx(190e-6)}
    # A window cuts events at its edges.
    half = trace_reduce.reduce_events(_synthetic(), (100e3, 450e3))
    assert half["busy_s"] == pytest.approx(100e-6)
    assert trace_reduce.reduce_events(
        {"devices": {}, "sync_ns": None}) is None


def test_trace_names():
    assert trace_reduce.program_name("jit_decode_chunk_paged(123)") == \
        "decode_chunk_paged"
    assert trace_reduce.program_name("jit_step_on_mesh") == "step_on_mesh"
    # An operation's event is named by its whole HLO text.
    attn = ('%attn.160 = bf16[32,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} '
            'custom-call(s32[32,37]{1,0} %get-tuple-element.6364), '
            'custom_call_target="tpu_custom_call", operand_layout_constrai')
    assert trace_reduce.op_name(attn) == "tpu_custom_call:attn.160"
    assert trace_reduce.is_kernel(trace_reduce.op_name(attn))
    other = ('%custom-call.79 = s32[8,32]{1,0:T(8,128)} custom-call(), '
             'custom_call_target="AllocateBuffer"')
    assert trace_reduce.op_name(other) == "custom-call.79"
    assert not trace_reduce.is_kernel(trace_reduce.op_name(other))
    assert trace_reduce.op_name("%fusion.226 = bf16[8,512]{1,0} fusion("
                                "bf16[4096]{0} %p), kind=kOutput") == \
        "fusion.226"
    assert trace_reduce.DEVICE_PLANE.match("/device:TPU:0")
    assert not trace_reduce.DEVICE_PLANE.match("/host:CPU")


def test_trace_reduce_on_the_recorded_chip_trace():
    """One second of a real v5e trace of the serve cell (its provenance is
    in the file): a batched prefill, one whole decode chunk, two single
    prefills.  The expected numbers were read off the raw trace by hand
    (PR 23) before the reducer existed in this form."""
    with open(os.path.join(_HERE, "recorded_trace.json")) as f:
        events = json.load(f)
    r = trace_reduce.reduce_events(events)
    assert r["n_devices"] == 1
    assert r["program_ns"]["decode_chunk_paged"] == [615108278]
    assert r["program_ns"]["prefill_many"] == [226543811]
    assert len(r["program_ns"]["prefill_one"]) == 2
    # The decode program's only Pallas kernel is paged attention: 8 steps
    # x 16 layers of it, 3.49 ms a call, 73% of the chunk.
    calls = r["kernel_ns"]["decode_chunk_paged"]
    assert len(calls) == 128 and sum(calls) == 446526956
    assert set(r["kernel_ns"]) == {"decode_chunk_paged"}
    assert r["device_ops"][0] == ["tpu_custom_call:attn",
                                  pytest.approx(0.446526956)]
    assert "while" not in dict(r["device_ops"])
    assert r["busy_s"] == pytest.approx(0.925303684)
    assert r["window_s"] == pytest.approx(0.954108896)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.0302, abs=1e-4)
    assert sum(dict(r["idle_gaps"]).values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    # A window over the decode chunk alone: busy all but 16 ms of it.
    t0 = 282030000.0
    chunk = trace_reduce.reduce_events(events, (t0, t0 + 615108278.0))
    assert 0.97 < chunk["busy_s"] / chunk["window_s"] <= 1.0


# ---- operations and bytes, against hand arithmetic ---------------------------


def test_costs_mistral_by_hand():
    s = model_sizes(_config("mistral-7b-v0.3-l16"))
    layer = 4096 * (32 + 16) * 128 + 32 * 128 * 4096 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert kernel_costs.matmul_params(s) == 16 * layer + 32768 * 4096
    # 7.52 GB of bf16 weights; 64 KiB of KV a token at 16 layers.
    assert kernel_costs.weight_bytes(s) == pytest.approx(7.517e9, rel=1e-3)
    assert kernel_costs.kv_bytes_per_token(s) == 65536
    # One paged call over 8192 resident tokens: K and V read once.
    flops, nbytes = kernel_costs.paged_decode_cost(s, 32, 8192)
    assert flops == 4 * 8192 * 32 * 128
    assert nbytes == 2 * 8192 * 8 * 128 * 2 + 2 * 32 * 32 * 128 * 2
    peak = kernel_costs.peaks("TPU v5 lite")
    least, bound = kernel_costs.roofline_seconds(flops, nbytes, peak)
    assert bound == "memory" and least == pytest.approx(nbytes / 819e9)


def test_costs_internlm2_by_hand():
    s = model_sizes(_config("internlm2-1.8b"))
    layer = 2048 * (16 + 16) * 128 + 16 * 128 * 2048 + 3 * 2048 * 8192
    assert kernel_costs.matmul_params(s) == 24 * layer + 92544 * 2048
    assert kernel_costs.matmul_params(s) == pytest.approx(1.70e9, rel=3e-3)
    per_token = kernel_costs.train_flops_per_token(s, 2048)
    attn = 6 * 2048 * 16 * 128 * 24
    assert per_token == 6 * kernel_costs.matmul_params(s) + attn
    assert per_token == pytest.approx(10.8e9, rel=5e-3)
    # All parameters: 1.89 B (the embedding is held, not multiplied).
    assert kernel_costs.weight_bytes(s) / 2 == pytest.approx(1.889e9,
                                                             rel=2e-3)
    flops, nbytes = kernel_costs.flash_forward_cost(s, 4, 2048)
    assert flops == 4 * 4 * 16 * 2048 * 2048 * 128 / 2
    assert nbytes == 4 * 2048 * 128 * (32 + 16) * 2 + 4 * 16 * 2048 * 4
    least, bound = kernel_costs.roofline_seconds(
        flops, nbytes, kernel_costs.peaks("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(flops / 197e12)


def test_an_unknown_device_has_no_peaks():
    assert kernel_costs.peaks("TPU v5 lite")["source"]
    with pytest.raises(KeyError, match="no peaks on record"):
        kernel_costs.peaks("cpu")


# ---- the plain reference --------------------------------------------------------


def test_reference_agrees_with_llama_model_at_tiny_widths():
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import dense_decoder
    from ray_tpu.models.llama import LlamaModel, cross_entropy_loss

    conf = json.load(open(os.path.join(_HERE, "cells", "configs",
                                       "tiny.json")))
    conf.update(num_key_value_heads=2)        # grouped-query, as the cells
    sizes = model_sizes(conf)
    cfg = llama_config(sizes)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))
    tokens = np.random.default_rng(3).integers(1, 256, size=(2, 48))
    want = np.asarray(model.apply(params, jnp.asarray(tokens)))
    for row in range(2):
        got = np.asarray(dense_decoder.logits(params, sizes, tokens[row]))
        # float32 on both sides: differences are rounding only.
        assert np.abs(got - want[row]).max() < 2e-4
    rows = [5, 47]
    some = np.asarray(dense_decoder.logits(params, sizes, tokens[0], rows))
    assert np.abs(some - want[0][rows]).max() < 2e-4
    # Right-padding a causal sequence changes nothing before the padding.
    padded = np.concatenate([tokens[0], np.zeros(16, int)])
    again = np.asarray(dense_decoder.logits(params, sizes, padded, rows))
    assert np.abs(again - some).max() < 1e-5
    loss = dense_decoder.mean_token_loss(params, sizes, tokens[:, :-1],
                                         tokens[:, 1:])
    theirs = float(cross_entropy_loss(
        model.apply(params, jnp.asarray(tokens[:, :-1])),
        jnp.asarray(tokens[:, 1:])))
    assert loss == pytest.approx(theirs, abs=1e-4)


# ---- the command ----------------------------------------------------------------


def test_the_command_refuses_to_measure_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("TPU_VISIBLE_CHIPS", None)
    out = subprocess.run(
        [sys.executable] + BENCH["command"][1:]
        + ["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
           "--trace", "0"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "nothing is measured without the chip" in out.stderr
    assert "metrics" not in out.stdout and "correct" not in out.stdout


# ---- one CPU rehearsal: the drivers end to end at TINY widths -----------------


# The rehearsal's cells by the kind of their mix: the TINY configuration
# under each driver, and a made-up family's under two of them.
TINY_OF_KIND = {"serve_open": ["tiny.open", "other.open", "miswired.open"],
                "serve_closed": ["tiny.closed"],
                "train_fit": ["tiny.train", "other.train"]}


def _stand_in_for_the_cells(bench, cells=None):
    """Each rehearsal cell reports what the cells of its kind report: every
    cell of `cells` (BENCHMARK.json's `workloads`) is stood in for by the
    tiny cells of its mix's kind, so a metric may list several cells of
    one kind."""
    stand_in = {}
    for w in cells or BENCH["workloads"]:
        with open(loader.find_file(BENCH, _REPO, "traffic",
                                   w["traffic"] + ".json")) as f:
            stand_in[w["name"]] = TINY_OF_KIND.get(json.load(f)["kind"], [])
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({t for w in m["workloads"]
                                     for t in stand_in[w]})
    return stand_in


def test_a_metric_may_list_two_cells_of_one_kind():
    cells = BENCH["workloads"] + [
        dict(BENCH["workloads"][0], name="a-second-open-loop-cell")]
    bench = json.loads(json.dumps(BENCH))
    listed = [m for m in bench["end_to_end"] + bench["per_layer"]
              if CELLS[0] in m.get("workloads", [])]
    for m in listed:
        m["workloads"].append("a-second-open-loop-cell")
    stand_in = _stand_in_for_the_cells(bench, cells)
    assert stand_in["a-second-open-loop-cell"] == stand_in[CELLS[0]]
    assert listed and all(
        m["workloads"] == sorted(stand_in[CELLS[0]]) for m in listed)


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One in-process cluster that offers `TPU: 1` (conftest's seam gives
    such a lease-holder the CPU), and a benchmark whose cells are the
    test-only TINY configuration under the harness's own three drivers,
    and a made-up family's configuration (its files copied to a directory
    of `paths` of their own) under two of them."""
    import ray_tpu
    from tests.conftest import _fast_config

    root = tmp_path_factory.mktemp("rehearsal")
    mixes = {"open": "tiny-open", "closed": "tiny-closed",
             "train": "tiny-packed"}
    bench = json.loads(json.dumps(BENCH))
    bench["paths"] = ["tests/benchmarks/cells", "extra"]
    bench["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                         "file": "tests/benchmarks/cells/configs/tiny.json",
                         "why": "test"},
                        {"name": "other-tiny", "source": "test",
                         "reduced": ["n_layer"], "why": "test",
                         "file": "extra/configs/other-tiny.json"},
                        {"name": "miswired-tiny", "source": "test",
                         "reduced": ["n_layer"], "why": "test",
                         "file": "extra/configs/miswired-tiny.json"}]
    bench["workloads"] = [{"name": "tiny." + k, "config": "tiny",
                           "traffic": v, "chips": 1, "why": "rehearsal"}
                          for k, v in mixes.items()]
    bench["workloads"] += [{"name": "other." + k, "config": "other-tiny",
                            "traffic": "other-" + v, "chips": 1,
                            "why": "a made-up family"}
                           for k, v in (("open", "open"),
                                        ("train", "packed"))]
    bench["workloads"].append(
        {"name": "miswired.open", "config": "miswired-tiny",
         "traffic": "other-open", "chips": 1, "why": "a broken timed path"})
    shutil.copytree(os.path.join(_HERE, "made_up"), root / "extra")
    _stand_in_for_the_cells(bench)
    os.symlink(os.path.join(_REPO, "tests"), root / "tests")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    ray_tpu.init(num_cpus=4, resources={"TPU": 1}, config=_fast_config())
    yield str(root)
    ray_tpu.shutdown()


def _rehearse(root, name, trace):
    lines = []
    cell = loader.load_cell(name, root)
    result = bench_run.run_cell(
        cell, 2 ** 31 + 7, 2.0, trace, time.monotonic(), platform="cpu",
        log=lambda **kw: lines.append(kw))
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"], lines
    assert result["attempted"] > 0 and result["failed"] == 0
    # Every result names what it ran on; the rehearsal ran on the CPU and
    # could never be taken for a measurement.
    assert result["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(result["device"])
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and math.isfinite(m["value"])
    json.dumps(result)
    return cell, result, lines


def test_rehearsal_serve_traced(rehearsal):
    cell, result, lines = _rehearse(rehearsal, "tiny.open", True)
    # Per-layer metrics that need no device trace are read from the spans.
    assert {"worker_ready_s", "handle_overhead_ms", "engine_ttft_ms",
            "client_ttft_p90_ms", "batch_occupancy"} <= set(result["metrics"])
    # The client's TTFT is a per-layer reading, no longer judged end to end.
    assert result["metrics"]["client_ttft_p90_ms"]["value"] > 0
    assert "ttft_p90_ms" not in {m["name"] for m in cell.end_to_end}
    assert 0 < result["metrics"]["batch_occupancy"]["value"] <= 100
    load = next(ln for ln in lines if ln.get("phase") == "load")
    assert load["compiles_in_window"] == 0
    # float32 on the CPU: the engine's tokens are the reference's argmax.
    assert all(c["max_logit_gap"] == 0.0 for c in load["reference"])
    # The profiler ran in the replica and its clock was tied to the spans.
    assert load["trace_marks"]["t1_mono_ns"] > load["trace_marks"]["t0_mono_ns"]


def test_rehearsal_closed_loop(rehearsal):
    cell, result, lines = _rehearse(rehearsal, "tiny.closed", False)
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end} \
        == {"batch_tokens_per_s", "setup_s"}
    assert result["metrics"]["batch_tokens_per_s"]["value"] > 0


def test_rehearsal_train(rehearsal):
    cell, result, lines = _rehearse(rehearsal, "tiny.train", False)
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert result["metrics"]["setup_s"]["value"] > 0
    load = next(ln for ln in lines if ln.get("phase") == "load")
    assert load["reports_received"] == load["steps"] == result["attempted"]
    assert load["reference_loss"] == pytest.approx(load["model_loss"],
                                                   abs=1e-3)


def test_a_made_up_family_is_found_from_files_alone(rehearsal):
    """A family that no file of `benchmarks/` knows: the same decoder under
    other key names, heads of 64, a tied head; its family file, its
    configuration, its reference and its mixes lie in a directory of
    `paths` of their own."""
    cell = loader.load_cell("other.open", rehearsal)
    assert cell.family_name == cell.config["family"] == "other_decoder"
    assert cell.family.__file__ == os.path.join(
        rehearsal, "extra", "families", "other_decoder.py")
    assert cell.family.reference.__file__ == os.path.join(
        rehearsal, "extra", "reference", "other_decoder_ref.py")
    sizes = cell.family.sizes(cell.config)
    assert sizes["vocab_size"] == 256 and "hidden_size" not in sizes
    assert cell.family.program_config(sizes).head_dim == 64
    # The general rules hold of it, and its family's own: the harness's
    # family refuses the same file (it has none of its keys).
    loader.check_configuration(cell.config, cell.family)
    with pytest.raises(KeyError):
        loader.load_family("dense_decoder").check_file(cell.config)
    with pytest.raises(loader.BenchmarkError, match="families/nowhere.py"):
        loader.load_family("nowhere", rehearsal)


@pytest.mark.parametrize("name", ["other.open", "other.train"])
def test_a_made_up_family_rehearses(rehearsal, name):
    cell, result, lines = _rehearse(rehearsal, name, False)
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    load = next(ln for ln in lines if ln.get("phase") == "load")
    if name == "other.open":
        assert load["reference"] and all(
            c["max_logit_gap"] == 0.0 for c in load["reference"])
    else:
        assert load["reference_loss"] == pytest.approx(load["model_loss"],
                                                       abs=1e-3)


def test_a_run_whose_timed_path_is_broken_is_not_correct(rehearsal):
    """The whole of a run but the look for a chip, over an engine built with
    another rotary base than the model's: every request completes, the
    streams are fluent, and `correct` comes out false with the gaps."""
    lines = []
    cell = loader.load_cell("miswired.open", rehearsal)
    result = bench_run.run_cell(
        cell, 2 ** 31 + 7, 2.0, False, time.monotonic(), platform="cpu",
        log=lambda **kw: lines.append(kw))
    assert result["correct"] is False and result["failed"] == 0
    problems = next(ln for ln in lines if ln.get("phase") == "check")
    assert any("under the reference's best logit" in p
               for p in problems["problems"])
    load = next(ln for ln in lines if ln.get("phase") == "load")
    assert all(c["over"] and c["kept_max_gap"] > 3 * 0.125
               for c in load["reference"])


def test_rehearsal_refuses_another_platform(rehearsal):
    cell = loader.load_cell("tiny.open", rehearsal)
    with pytest.raises(loader.BenchmarkError, match="measures 'tpu'"):
        bench_run.run_cell(cell, 1, 1.0, False, time.monotonic(),
                           log=lambda **kw: None)
