"""The per-layer metrics that read the chip's own row of the program's
spans (PR 55): `benchmarks/layer_metrics/chip_programs.py` and its six
readers, on a span file written by hand, against numbers worked out by
hand; and nothing where a program records no `chip.program`.
"""

import json
import os
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from benchmarks.harness import loader  # noqa: E402

METRICS = ("prefill_chip_share", "prefill_chip_ms_per_ktok",
           "chip_starved_share")
WINDOW = (100.0, 110.0)          # seconds of time.monotonic()
# The readers take the window, not the traced slot: this one holds nothing.
OBS = {"window": WINDOW, "trace": {"window_mono_s": (120.0, 121.0)}}


def _reader(name):
    return loader.sibling_reader(
        os.path.join(_REPO, "benchmarks", "layer_metrics", "x.py"), name)


def _span(sid, name, t0_s, dur_ms, thread="llm-engine", parent=None,
          **attrs):
    s = {"id": sid, "parent": parent, "name": name,
         "t0_ns": int(round(t0_s * 1e9)), "dur_ns": int(round(dur_ms * 1e6)),
         "tid": 1, "thread": thread}
    if attrs:
        s["attrs"] = attrs
    return s


def _program(sid, seq, kind, t0_s, dur_ms, starved_ms=0.0, **attrs):
    return _span(sid, "chip.program", t0_s, dur_ms,
                 thread="llm-engine-watch", kind=kind, seq=seq,
                 queued_ns=int(round((t0_s - 0.001) * 1e9)),
                 starved_ns=int(round(starved_ms * 1e6)), seen_by="watch",
                 late_ns=0, **attrs)


def _prefill(sid, seq, t0_s, dur_ms, tokens, starved_ms=0.0):
    return _program(sid, seq, "prefill", t0_s, dur_ms, starved_ms,
                    bucket=2048, rows=1, width=1, computed=2048,
                    prompt_tokens=tokens, rids=[seq])


LOOP = [
    _span(1, "engine.pass", 99.0, 6000),
    # asleep for want of a request over the second half of the starved
    # second below; the backpressure before it is the host's own doing
    _span(2, "engine.idle", 105.0, 200, why="backpressure"),
    _span(3, "engine.idle", 105.5, 500, why="no_request"),
    _span(4, "engine.pass", 106.0, 3000),
]
CHIP = [
    # before the window: counted by nothing
    _prefill(10, 1, 98.0, 300, 900),
    # straddles the window's start: its second half is in the window, and
    # it ENDS there, so its whole second is a cost of its 2,000 tokens;
    # what starved the chip before it lies outside
    _prefill(11, 2, 99.5, 1000, 2000, starved_ms=400),
    _program(12, 3, "decode", 100.5, 3500, active=2, steps=16),
    _prefill(13, 4, 104.0, 500, 500),
    _program(14, 5, "decode", 104.5, 500, active=3, steps=24),
    # the chip had nothing queued from 105.0 to 106.0
    _program(15, 6, "decode", 106.0, 3000, starved_ms=1000, active=3,
             steps=24),
    # ends after the window: no cost a thousand tokens, 1 s of share
    _prefill(16, 7, 109.0, 2000, 4000),
]


def _write(path, spans):
    path.parent.mkdir(parents=True, exist_ok=True)
    lines = [{"header": {"pid": 7, "label": "w", "time_s": 5000.0,
                         "mono_ns": int(100e9)}}] + spans
    path.write_text("".join(json.dumps(x) + "\n" for x in lines))


@pytest.fixture
def session(tmp_path, monkeypatch):
    monkeypatch.setenv("RAY_TPU_TEMP_DIR", str(tmp_path))
    _write(tmp_path / "session-a" / "logs" / "spans-w1.jsonl", LOOP + CHIP)
    return tmp_path


@pytest.mark.parametrize("name, by_hand", [
    # (0.5 + 0.5 + 1.0) s of prefill programs in a window of 10 s
    ("prefill_chip_share", 20.0),
    # (1000 + 500) ms for (2,000 + 500) tokens
    ("prefill_chip_ms_per_ktok", 600.0),
    # 1 s starved, half of it asleep for want of a request, of 10 s
    ("chip_starved_share", 5.0),
])
@pytest.mark.parametrize("twin", ["", ".closed"])
def test_reader_gives_the_number_worked_out_by_hand(session, name, by_hand,
                                                    twin):
    assert _reader(name + twin).read(OBS) == pytest.approx(by_hand)


@pytest.mark.parametrize("name", METRICS)
def test_reader_finds_nothing_where_there_is_nothing(session, tmp_path,
                                                     monkeypatch, name):
    read = _reader(name).read
    # A program that records no `chip.program` (any parent of PR 55): its
    # loop's spans are there, the chip's row is not.
    _write(tmp_path / "session-a" / "logs" / "spans-w1.jsonl", LOOP)
    assert read(OBS) is None
    # No window, no span in it, no file at all.
    assert read({}) is None and read({"window": None}) is None
    assert read({"window": (200.0, 210.0)}) is None
    monkeypatch.setenv("RAY_TPU_TEMP_DIR", str(tmp_path / "nowhere"))
    assert read(OBS) is None


def test_no_prompt_token_ended_in_the_window_is_no_cost(session, tmp_path):
    # Decode only (the slot of reason-closed, were the window that short).
    _write(tmp_path / "session-a" / "logs" / "spans-w1.jsonl",
           LOOP + [p for p in CHIP if p["attrs"]["kind"] == "decode"])
    assert _reader("prefill_chip_ms_per_ktok").read(OBS) is None
    assert _reader("prefill_chip_share").read(OBS) == 0.0


def test_the_six_entries_stand_at_the_end_of_per_layer():
    bench = loader.load_benchmark()
    last = bench["per_layer"][-6:]
    assert [m["name"] for m in last] == [
        n + twin for n in METRICS for twin in (".closed", "")]
    closed = [w["name"] for w in bench["workloads"]
              if w["name"].endswith("-closed")]
    for m in last:
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["workloads"] == (closed if m["name"].endswith(".closed")
                                  else ["mistral7b-serve-chat-open"])
        reader = _reader(m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == \
            (m["layer"], m["unit"], m["moves"])


# ---- engine -> span files -> readers, on the CPU ----------------------------


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """An in-process cluster that offers `TPU: 1` (conftest's seam gives
    such a lease-holder the CPU) and a benchmark whose one cell is the
    test-only tiny dense configuration under the tiny closed-loop mix,
    reporting what `mistral7b-serve-docs-closed` reports."""
    import ray_tpu
    from tests.conftest import _fast_config

    root = tmp_path_factory.mktemp("chip_program_rehearsal")
    bench = json.loads(json.dumps(loader.load_benchmark()))
    bench["paths"] = ["tests/benchmarks/cells"]
    bench["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                         "file": "tests/benchmarks/cells/configs/tiny.json",
                         "why": "test"}]
    bench["workloads"] = [{"name": "tiny.closed", "config": "tiny",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "rehearsal"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.closed"] \
                if "mistral7b-serve-docs-closed" in m["workloads"] else []
    os.symlink(os.path.join(_REPO, "tests"), root / "tests")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    ray_tpu.init(num_cpus=4, resources={"TPU": 1}, config=_fast_config())
    yield str(root)
    ray_tpu.shutdown()


@pytest.mark.time_limit(360)
def test_a_traced_closed_loop_line_carries_the_three_closed_metrics(
        rehearsal):
    """The whole of a traced run but the look for a chip: the engine of a
    replica writes its `chip.program` spans into the session's files and
    the line has what the readers make of them over the window.  (A CPU
    run: the values are no measurement of anything; their bounds hold on
    any backend.)"""
    import time

    from benchmarks import run as bench_run

    lines = []
    cell = loader.load_cell("tiny.closed", rehearsal)
    result = bench_run.run_cell(
        cell, 2 ** 31 + 55, 2.0, True, time.monotonic(), platform="cpu",
        log=lambda **kw: lines.append(kw))
    assert result["correct"], lines
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert {m + ".closed" for m in METRICS} <= set(got)
    assert not set(METRICS) & set(got)      # the open-loop twins: not here
    share, cost, starved = (got[m + ".closed"] for m in METRICS)
    assert 0 < share < 100 and 0 <= starved < 100 and share + starved < 100
    assert cost > 0
    units = {k: v["unit"] for k, v in result["metrics"].items()}
    assert units["prefill_chip_ms_per_ktok.closed"] == "ms/ktok"
