"""The `minicpm_sala` family's part of the benchmark: its configuration file
against the catalog's keys, its cost functions by hand, its readers on
hand-made observations and span files, and a CPU rehearsal of
`minicpmsala-serve-longdocs-closed` at tiny widths through the harness's own
closed-loop driver.  Every entry of `BENCHMARK.json` is looked up by NAME
and what the cell reports is compared as a superset, as
`test_mla_moe_cell.py` does, so that the next cell to be appended needs no
fixture to hide it from this module.
"""

import json
import os
import sys
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import kernel_costs, loader  # noqa: E402

CELL = "minicpmsala-serve-longdocs-closed"
CONFIG = "minicpm-sala-l8"
BENCH = loader.load_benchmark()
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json"
NEW_METRICS = {
    "sala_decode_roofline": ("model step", "device_trace"),
    "sala_sparse_attn_roofline": ("kernels", "device_trace"),
    "sala_prefill_mfu": ("model step", "device_trace"),
    "sparse_pages_read_share": ("engine", "program_counter")}
LISTED = {"batch_occupancy.closed", "prefill_device_ms.closed",
          "decode_step_ms.closed", "device_idle.closed",
          "queue_wait_ms.closed", "loop_host_ms.closed",
          "admit_host_ms.closed", "paged_live_share.closed"}
SLOT = (10.0, 12.0)


def _named(entries, name):
    return next(e for e in entries if e["name"] == name)


@pytest.fixture(scope="module")
def cell():
    return loader.load_cell(CELL)


@pytest.fixture(scope="module")
def costs(cell):
    return cell.readers["sala_decode_roofline"].costs


# ---- the configuration, the mix and the cell, as the issue names them ------


def test_the_cell_is_as_named(cell):
    entry = _named(BENCH["workloads"], CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        (CONFIG, "longdocs-closed", 1)
    assert len(entry["why"]) <= 200
    conf = _named(BENCH["configs"], CONFIG)
    assert conf["source"] == SOURCE == cell.config["source"]
    assert conf["reduced"] == ["num_hidden_layers"] == cell.config["reduced"]
    assert conf["file"] == "benchmarks/configs/minicpm-sala-l8.json"
    assert len(conf["why"]) <= 200
    t = cell.traffic
    assert (t["kind"], t["clients"], t["pool_requests"]) == \
        ("serve_closed", 12, 240)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 12288,
                                  "sigma": 0.5, "min": 4096, "max": 32768}
    assert t["output_tokens"] == {"dist": "uniform", "min": 256, "max": 512}
    assert t["sampling"] == "greedy" and t["shared_prefixes"] is False
    assert t["order_seed"] == 20261003
    others = [json.load(open(os.path.join(_REPO, "benchmarks", "traffic", f)))
              for f in os.listdir(os.path.join(_REPO, "benchmarks",
                                               "traffic"))]
    assert [o["order_seed"] for o in others].count(t["order_seed"]) == 1
    engine = cell.config["serve"]["engine"]
    assert engine["max_batch"] == t["clients"] == 12
    # the longest prompt and answer; every slot's worst case in pages
    assert engine["max_len"] == 32768 + 512 == 33280
    assert engine["kv_pool_tokens"] == 12 * 33280 == 399360
    assert engine["page_size"] == cell.config["sparse_config"]["block_size"]
    assert [m["name"] for m in cell.end_to_end] == ["batch_tokens_per_s",
                                                    "setup_s"]


def test_the_mix_lies_on_both_sides_of_dense_len(cell):
    """Four fifths of the prompts past dense_len, a fifth under it, in the
    four buckets the engine compiles."""
    from benchmarks.harness import traffic

    reqs = traffic.serve_requests(cell.traffic, 7, 73448, 45)
    lens = [len(r.prompt_tokens) for r in reqs]
    past = sum(n > 8192 for n in lens) / len(lens)
    assert 0.75 < past < 0.85
    assert traffic.buckets_of(cell.traffic, 64, 33280) == \
        [4096, 8192, 16384, 32768]
    assert min(lens) == 4096 and max(lens) == 32768


def test_the_benchmark_holds_the_cell_by_name():
    reported = {m["name"] for m in BENCH["per_layer"]
                if "workloads" not in m or CELL in m["workloads"]}
    assert reported >= set(NEW_METRICS) | LISTED | {"worker_ready_s"}
    assert not {m for m in reported if m.startswith(("moe_", "mla_"))}
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
                   if r["name"] == "MiniCPM-SALA")
    assert row["source_url"] == SOURCE
    conf = cell.config
    differ = {k for k, v in row["config"].items() if conf.get(k) != v}
    # (the list of mixers follows the depth: `reduced` names the number,
    # `published` holds both)
    assert differ == {"num_hidden_layers", "mixer_types"}
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["published"] == {
        "num_hidden_layers": 32, "mixer_types": row["config"]["mixer_types"]}
    assert conf["mixer_types"] == row["config"]["mixer_types"][9:17]
    assert conf["num_hidden_layers"] == 8
    assert conf["mixer_types"].count("minicpm4") == 2
    for key in ("sparse_config", "lightning_decay", "output_norm", "gates",
                "rotary", "weights", "selection_tie"):
        assert key in conf["assumed"], key
    d = conf["deployment"]
    assert d["chips_sharing_a_layer"] == 1 and d["chips_in_all"] == 4
    assert "BOTH" in d["what"]
    loader.check_configuration(conf, cell.family)
    assert cell.family.layer_pattern(conf) == (0, 4)


def test_a_file_of_the_family_is_held_to_its_own_rules(cell):
    def refused(match, **change):
        with pytest.raises((ValueError, loader.BenchmarkError), match=match):
            loader.check_configuration(dict(cell.config, **change),
                                       cell.family)

    kept = cell.config["mixer_types"]
    refused("no contiguous slice",
            mixer_types=kept[:2] + kept[:1] + kept[1:6])
    refused("one 'minicpm4' layer in four",
            mixer_types=cell.config["published"]["mixer_types"][1:9])
    refused("for each of", num_hidden_layers=7)
    refused("keep no whole period", num_hidden_layers=3,
            mixer_types=kept[:3])
    refused("lets only", reduced=["num_hidden_layers", "hidden_size"],
            published=dict(cell.config["published"], hidden_size=8192))
    refused("every width is the published one",
            published=dict(cell.config["published"], hidden_size=8192))
    refused("is not under its published value",
            published=dict(cell.config["published"], num_hidden_layers=8))
    refused("without a position term", attn_use_rope=True)
    refused("gate their output", use_output_gate=False)
    refused("heads of the sparse layers' size", lightning_nh=16)
    refused("a page is a block", serve={"engine": dict(
        cell.config["serve"]["engine"], page_size=128)})
    refused("whole blocks in the window", sparse_config=dict(
        cell.config["sparse_config"], window_size=2000))


def test_costs_by_hand(cell, costs):
    sizes = cell.family.sizes(cell.config)
    assert costs.layers(sizes) == {"all": 8, "sparse": 2, "lightning": 6}
    assert costs.kept_blocks(sizes) == 1 + 32 + 64 == 97
    mm = costs.matmul_params(sizes)
    # ISSUE 49's arithmetic: q, o, gate 16.8 M each, k and v 1.05 M each
    assert mm["sparse"] == 3 * 4096 * 4096 + 2 * 4096 * 256
    assert mm["lightning"] == 5 * 4096 * 4096
    assert mm["ffn"] == 3 * 4096 * 16384
    assert costs.parameters(sizes) == 2_820_545_280
    assert costs.parameters(sizes) * 2 / 1e9 == pytest.approx(
        cell.config["memory"]["weights_gb"], abs=2e-3)
    # a decode step reads every weight but the embedding's rows
    assert costs.step_weight_bytes(sizes) == \
        (2_820_545_280 - 73448 * 4096) * 2
    # a page of one K/V head of one layer: K and V of 64 tokens of 128
    assert costs.page_bytes(sizes) == 2 * 64 * 128 * 2 == 32768
    # six lightning layers of 32 x 128 x 128 float32: 12.6 MB a sequence
    assert costs.state_bytes_per_slot(sizes) == 6 * 32 * 128 * 128 * 4
    assert costs.state_bytes_per_slot(sizes) == \
        cell.config["memory"]["recurrent_bytes_per_sequence"]
    assert costs.token_flops(sizes) == 2.0 * (
        2 * mm["sparse"] + 6 * mm["lightning"] + 8 * mm["ffn"])
    assert 4.3e9 < costs.token_flops(sizes) < 4.5e9
    # a query over a page: 16 heads x 64 tokens x 128 x (scores, values) x 2
    assert costs.page_flops(sizes) == 4 * 16 * 64 * 128
    assert costs.recurrence_flops(sizes) == 4 * 32 * 128 * 128
    # a decode step at 12 rows, all past dense_len at about 14k tokens:
    # 97 pages a table, four tables a row; the weights (5.04 GB) bind it
    # beside 0.15 GB of pages and 0.30 GB of state: 6.7 ms
    pages, keys = 12 * 4 * 97, 12 * 4 * 870
    flops, nbytes = costs.decode_step_cost(sizes, 12, pages, keys, 12 * 4)
    assert nbytes == costs.step_weight_bytes(sizes) \
        + (pages + 48) * 32768 + keys * 256 \
        + 2 * 12 * costs.state_bytes_per_slot(sizes)
    least, bound = kernel_costs.roofline_seconds(
        flops, nbytes, kernel_costs.peaks("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(6.72e-3, rel=1e-2)
    # the kernel's calls of that step: the selected pages' bytes bind them
    flops, nbytes = costs.sparse_kernel_cost(sizes, 12 * 2, pages, 48)
    assert flops == pages * 4 * 16 * 64 * 128
    assert nbytes == (pages + 48) * 32768 + 2 * 24 * 32 * 128 * 4
    assert flops / nbytes == pytest.approx(15.8, rel=2e-2)
    # a prompt: every block up to its own under dense_len; past it at most
    # 97 a query
    assert costs.kept_pairs(sizes, 128) == 64 * 1 + 64 * 2
    assert costs.kept_pairs(sizes, 8192) == 64 * sum(range(1, 129))
    assert costs.kept_pairs(sizes, 16384) == \
        64 * sum(range(1, 98)) + (16384 - 97 * 64) * 97
    n = 16384
    assert costs.prefill_flops(sizes, n) == pytest.approx(
        n * costs.token_flops(sizes)
        + 2 * 2 * (costs.kept_pairs(sizes, n) * 4 * 16 * 64 * 128
                   + 2 * 16 * (n * n / 32) * 128)
        + 6 * n * 4 * 32 * 128 * 128 + 2 * 4096 * 73448, rel=1e-12)
    # the selected blocks' attention, the selection and the recurrence
    # are a twenty-fifth of what a prompt requires
    assert 0.03 < 1 - n * costs.token_flops(sizes) \
        / costs.prefill_flops(sizes, n) < 0.05


# ---- the readers ------------------------------------------------------------


def _span(sid, name, t0_s, dur_ms, **attrs):
    return {"id": sid, "parent": None, "name": name,
            "t0_ns": int(t0_s * 1e9), "dur_ns": int(dur_ms * 1e6), "tid": 1,
            "thread": "llm-engine", "attrs": attrs}


def _chunk(rows_sparse, rows_dense, dense_pages):
    """What a chunk of 8 steps counts with so many rows on either side of
    dense_len (a dense row holding `dense_pages` pages, a sparse one 220
    and seeing 870 compressed keys)."""
    read = 8 * 4 * (rows_sparse * 97 + rows_dense * dense_pages)
    resident = 8 * 4 * (rows_sparse * 220 + rows_dense * dense_pages)
    return dict(sparse_pages_read=read, sparse_pages_resident=resident,
                sparse_rows=8 * rows_sparse,
                compressed_keys_read=8 * 4 * rows_sparse * 870)


@pytest.fixture
def spans(tmp_path, monkeypatch):
    """A session whose engine counted: two chunks of 8 steps in the traced
    slot, one before it in the window, one of another time."""
    monkeypatch.setenv("RAY_TPU_TEMP_DIR", str(tmp_path))
    logs = tmp_path / "session-a" / "logs"
    logs.mkdir(parents=True)
    lines = [{"header": {"pid": 7, "label": "w", "time_s": 5000.0,
                         "mono_ns": int(8e9)}}] + [
        _span(1, "engine.decode.wait", 1.0, 80, **_chunk(12, 0, 0)),
        _span(2, "engine.decode.wait", 9.0, 80, **_chunk(8, 4, 100)),
        _span(3, "engine.decode.wait", 10.1, 80, **_chunk(10, 2, 100)),
        _span(4, "engine.decode.wait", 10.6, 80, **_chunk(10, 2, 110))]
    (logs / "spans-w1.jsonl").write_text(
        "".join(json.dumps(x) + "\n" for x in lines))
    return tmp_path


def _obs(cell, **over):
    sizes = cell.family.sizes(cell.config)
    obs = {"sizes": sizes, "config": cell.config, "family": "minicpm_sala",
           "max_batch": 12, "window": (8.0, 14.0),
           "peaks": kernel_costs.peaks("TPU v5 lite"),
           # (t, slots taken, queued, streams decoding, their tokens)
           "samples": [(10.0 + i / 20, 12, 0, 12, 170_000)
                       for i in range(40)] + [(13.0, 2, 0, 2, 100)],
           "replica_spans": [
               {"prompt_len": 16384, "first": 11.9},
               {"prompt_len": 6000, "first": 12.4},
               {"prompt_len": 9000, "first": 14.9},     # past the slot
               {"prompt_len": 2048, "first": None}],
           "trace": {"window_mono_s": SLOT,
                     # two whole chunks (two calls a step) and 5 calls more
                     "kernel_ns": {"decode_chunk_paged":
                                   [0.3e6] * (2 * 16 + 5)},
                     "program_ns": {"decode_chunk_paged": [72e6] * 2,
                                    "prefill_one": [1.5e9, 0.5e9]}}}
    obs.update(over)
    return obs


def test_readers_on_hand_made_observations(cell, costs, spans):
    sizes = cell.family.sizes(cell.config)
    peak = kernel_costs.peaks("TPU v5 lite")
    roof, attn, mfu, share = (cell.readers[n] for n in NEW_METRICS)
    # the slot's two chunks: a step's mean
    counts = roof.counts_per_step(_obs(cell), 8)
    assert counts["sparse_pages_read"] == 4 * (10 * 97 + 2 * 105)
    assert counts["sparse_rows"] == 10
    assert counts["compressed_keys_read"] == 4 * 10 * 870
    least = kernel_costs.roofline_seconds(*costs.decode_step_cost(
        sizes, 12, counts["sparse_pages_read"],
        counts["compressed_keys_read"], 48), peak)[0]
    # chunks of 8 steps in 72 ms: 9 ms a step
    assert roof.read(_obs(cell)) == pytest.approx(100 * least / 9e-3,
                                                  rel=1e-9)
    assert 70 < roof.read(_obs(cell)) < 80
    # the kernel: 37 calls are 18.5 steps of two; their least time at the
    # slot's pages a step, over 37 x 0.3 ms
    step = kernel_costs.roofline_seconds(*costs.sparse_kernel_cost(
        sizes, 24, counts["sparse_pages_read"], 48), peak)[0]
    assert attn.read(_obs(cell)) == pytest.approx(
        100 * step * 18.5 / (37 * 0.3e-3), rel=1e-9)
    assert 0 < attn.read(_obs(cell)) < 100
    # the slot's two runs are those of the two requests whose first token
    # left inside it or within the longest run after it: 16,384 and 6,000,
    # in 2.0 s of prefill programs
    assert mfu.read(_obs(cell)) == pytest.approx(
        100 * (costs.prefill_flops(sizes, 16384)
               + costs.prefill_flops(sizes, 6000)) / (2.0 * 197e12),
        rel=1e-9)
    assert 20 < mfu.read(_obs(cell)) < 30
    # three candidates for two runs (the first's prefill began before the
    # slot): the runs go to the two prompts whose work follows their
    # lengths, 0.5 s and 1.5 s, not to the short one that ended first
    shifted = _obs(cell, replica_spans=[
        {"prompt_len": 16000, "first": 10.2},
        {"prompt_len": 6000, "first": 10.9},
        {"prompt_len": 16384, "first": 12.6}])
    shifted["trace"]["program_ns"]["prefill_one"] = [0.5e9, 1.5e9]
    assert mfu.read(shifted) == pytest.approx(mfu.read(_obs(cell)),
                                              rel=1e-9)
    # fewer requests than runs: nothing to hold the runs to
    assert mfu.read(_obs(cell, replica_spans=[
        {"prompt_len": 6000, "first": 10.9}])) is None
    # the window's three chunks: pages read over pages resident
    read = sum(_chunk(*c)["sparse_pages_read"]
               for c in ((8, 4, 100), (10, 2, 100), (10, 2, 110)))
    resident = sum(_chunk(*c)["sparse_pages_resident"]
                   for c in ((8, 4, 100), (10, 2, 100), (10, 2, 110)))
    assert share.read(_obs(cell)) == pytest.approx(100 * read / resident,
                                                   rel=1e-12)
    assert 45 < share.read(_obs(cell)) < 55
    # nothing to read: no trace, another family's cell, a trace without
    # the programs -- None, never an error
    for reader in (roof, attn, mfu):
        assert reader.read(_obs(cell, trace=None)) is None
        assert reader.read(_obs(cell, family="mla_moe")) is None
        assert reader.read(_obs(cell, trace={
            "window_mono_s": SLOT, "kernel_ns": {},
            "program_ns": {}})) is None
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
    names = ("sala_decode_roofline", "sala_sparse_attn_roofline",
             "sparse_pages_read_share")
    for name in names:
        assert cell.readers[name].read(_obs(cell)) is None
    logs = tmp_path / "session-b" / "logs"
    logs.mkdir(parents=True)
    lines = [{"header": {"pid": 7, "label": "w", "time_s": 5000.0,
                         "mono_ns": int(8e9)}},
             _span(3, "engine.decode.wait", 10.1, 90, active=4, steps=32,
                   pages_live=10, pages_table=100)]
    (logs / "spans-w1.jsonl").write_text(
        "".join(json.dumps(x) + "\n" for x in lines))
    for name in names:
        assert cell.readers[name].read(_obs(cell)) is None


def test_a_checkout_without_the_model_is_told_so_at_once(tmp_path,
                                                         monkeypatch):
    """The parent commit with these benchmark files laid over it: loading
    the family raises `BenchmarkError` (the command exits 1) before any
    cluster or replica is started."""
    monkeypatch.setattr(loader, "REPO_ROOT", str(tmp_path))
    with pytest.raises(loader.BenchmarkError,
                       match="no ray_tpu/models/minicpm_sala.py"):
        loader.load_family("minicpm_sala", _REPO, BENCH)


# ---- the rehearsal ----------------------------------------------------------


def test_the_follow_tool_at_tiny_widths(monkeypatch, tmp_path, capsys):
    """`tools/minicpm_sala_follow.py` on the tiny twin, every position one
    to tell (FOLLOW_OVER under zero): in float32 the engine's tokens are
    both passes' best, the passes keep the same blocks in both sparse
    layers, and each told position names the four (layer, K/V head)
    tables of its step."""
    from benchmarks.tools import minicpm_sala_follow as tool

    monkeypatch.setenv("FOLLOW_TINY", "1")
    monkeypatch.setenv("FOLLOW_OVER", "-1")
    monkeypatch.setenv("FOLLOW_CASES", "5:5")       # a prompt of 98 tokens
    monkeypatch.setattr(tool, "OUT", str(tmp_path))
    assert tool.main() == 0
    said = {}
    for line in capsys.readouterr().out.splitlines():
        said.update(json.loads(line))
    assert said["prompt_len"] == 98 and said["tokens"] == 12
    assert said["engine_is_the_float32_pass_best_share"] == 1.0
    assert said["engine_is_the_served_pass_best_share"] == 1.0
    assert said["gaps_over_0.02"] == said["served_pass_gaps_over_0.02"] == []
    assert said["shift_median"] < 1e-3
    assert [(layer["selections"], layer["float32_and_served_pass_differ"])
            for layer in said["by_sparse_layer"]] == [(24, 0), (24, 0)]
    assert len(said["over"]) == 8
    assert all(len(o["tables"]) == 4 and o["gap"] == 0.0
               for o in said["over"])
    assert (tmp_path / "sala_follow.jsonl").read_text().count("\n") == 2


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """An in-process cluster that offers `TPU: 1` (conftest's seam gives
    such a lease-holder the CPU) and a benchmark whose one cell is the
    tiny `minicpm_sala` configuration under the tiny closed-loop mix,
    reporting what `minicpmsala-serve-longdocs-closed` reports."""
    import ray_tpu
    from tests.conftest import _fast_config

    root = tmp_path_factory.mktemp("minicpm_sala_rehearsal")
    bench = json.loads(json.dumps(BENCH))
    bench["paths"] = ["tests/benchmarks/minicpm_sala"]
    bench["configs"] = [{
        "name": "tiny-minicpm-sala", "source": "test", "reduced": [],
        "file": "tests/benchmarks/minicpm_sala/configs/"
                "tiny-minicpm-sala.json", "why": "test"}]
    bench["workloads"] = [{"name": "tiny.longdocs",
                           "config": "tiny-minicpm-sala",
                           "traffic": "tiny-longdocs-closed", "chips": 1,
                           "why": "rehearsal"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.longdocs"] if CELL in m["workloads"] \
                else []
    os.symlink(os.path.join(_REPO, "tests"), root / "tests")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    ray_tpu.init(num_cpus=4, resources={"TPU": 1}, config=_fast_config())
    yield str(root)
    ray_tpu.shutdown()


@pytest.mark.time_limit(360)
@pytest.mark.parametrize("trace", [False, True], ids=["timed", "traced"])
def test_rehearsal_longdocs_closed(rehearsal, trace):
    """The whole of a run but the look for a chip: replica up through
    serve.run, every bucket warmed, 4 clients on 4 slots for 2 s, drained,
    samples of up to four buckets (both regimes) against the reference,
    nothing compiled in the window."""
    lines = []
    cell = loader.load_cell("tiny.longdocs", rehearsal)
    assert cell.family.__file__ == os.path.join(
        _REPO, "benchmarks", "families", "minicpm_sala.py")
    result = bench_run.run_cell(
        cell, 2 ** 31 + 49, 2.0, trace, time.monotonic(), platform="cpu",
        log=lambda **kw: lines.append(kw))
    assert result["correct"], lines
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    load = next(ln for ln in lines if ln.get("phase") == "load")
    assert load["compiles_in_window"] == 0
    # float32 on the CPU: the engine's tokens are the reference's argmax
    assert load["reference"] and all(
        c["max_logit_gap"] == 0.0 for c in load["reference"])
    assert max(c["prompt_len"] for c in load["reference"]) > 64
    if trace:
        # (no device plane on the CPU: the readers of the trace find
        # nothing and leave their metrics out; the counters' reader reads
        # the engine's spans, which are there)
        assert {"worker_ready_s", "batch_occupancy.closed",
                "sparse_pages_read_share",
                "paged_live_share.closed"} <= set(result["metrics"])
        assert not {"sala_decode_roofline", "sala_prefill_mfu",
                    "sala_sparse_attn_roofline"} & set(result["metrics"])
        assert 30 < result["metrics"]["sparse_pages_read_share"]["value"] \
            < 100
    else:
        assert set(result["metrics"]) == {"batch_tokens_per_s", "setup_s"}
        assert result["metrics"]["batch_tokens_per_s"]["value"] > 0
