"""The per-layer metrics that read the program's own spans (PR 40):
`benchmarks/layer_metrics/program_spans.py` and its eight readers, on span
files written by hand, against numbers worked out by hand.
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

BENCH = loader.load_benchmark()
METRICS = ("queue_wait_ms", "loop_host_ms", "admit_host_ms",
           "paged_live_share")
CLOSED_CELLS = ["mistral7b-serve-docs-closed", "phi4flash-serve-reason-closed",
                "granite4h-serve-rows-closed"]

WINDOW = (100.0, 110.0)          # seconds of time.monotonic()
SLOT = (103.0, 105.0)            # the traced slot inside it
OBS = {"window": WINDOW, "trace": {"window_mono_s": SLOT}}


def _reader(name):
    return loader.sibling_reader(
        os.path.join(_REPO, "benchmarks", "layer_metrics", "x.py"), name)


def _span(sid, parent, name, t0_s, dur_ms, rid=None, **attrs):
    s = {"id": sid, "parent": parent, "name": name,
         "t0_ns": int(t0_s * 1e9), "dur_ns": int(dur_ms * 1e6), "tid": 1,
         "thread": "llm-engine"}
    if rid is not None:
        s["rid"] = rid
    if attrs:
        s["attrs"] = attrs
    return s


def _write(path, spans, pid=7, tail=""):
    path.parent.mkdir(parents=True, exist_ok=True)
    lines = [{"header": {"pid": pid, "label": "w", "time_s": 5000.0,
                         "mono_ns": int(100e9)}}] + spans
    path.write_text("".join(json.dumps(x) + "\n" for x in lines) + tail)


@pytest.fixture
def sessions(tmp_path, monkeypatch):
    """Two sessions under a temp dir of the test's own.  The newer holds the
    window: a replica's file in two halves and a second process whose span
    ids collide with the first's; the older holds spans of another time."""
    monkeypatch.setenv("RAY_TPU_TEMP_DIR", str(tmp_path))
    new = tmp_path / "session-new" / "logs"
    _write(new / "spans-w1.jsonl.1", [
        # warm-up, before the window: counted by nothing
        _span(1, None, "engine.pass", 99.0, 50),
        _span(2, 1, "engine.decode.wait", 99.01, 40, active=1, steps=8,
              pages_live=1000, pages_table=1000),
        _span(3, None, "request.queue", 100.5, 2, rid=1, prompt_len=9,
              deferred=False),
    ])
    _write(new / "spans-w1.jsonl", [
        _span(4, None, "request.queue", 101.0, 6, rid=2),
        _span(5, None, "request.queue", 104.0, 40, rid=3),
        _span(6, None, "request.queue", 111.0, 900, rid=4),    # after it
        # pass A: a decode chunk and nothing else; host-only 100 - 90 = 10
        _span(10, None, "engine.pass", 103.1, 100),
        _span(11, 10, "engine.decode.build", 103.1, 2),
        _span(12, 10, "engine.decode.dispatch", 103.102, 1),
        _span(13, 10, "engine.decode.wait", 103.103, 90, pages_live=10,
              pages_table=100),
        _span(14, 10, "engine.walk", 103.193, 3),
        # pass B: an admission that prefilled, then a chunk;
        # host-only 150 - 30 - 92 = 28; its admit 50 - 30 = 20
        _span(20, None, "engine.pass", 103.3, 150),
        _span(21, 20, "engine.admit", 103.3, 50, admitted=1, rids=[3]),
        _span(22, 21, "engine.prefill", 103.302, 45, bucket=64, rows=1),
        _span(23, 22, "engine.prefill.wait", 103.31, 30),
        _span(24, 20, "engine.decode.wait", 103.352, 92, pages_live=30,
              pages_table=100),
        # pass C: admitted, every stream's queue full: no chunk, so no
        # `loop_host_ms` sample; its admit 55 - 46 = 9
        _span(30, None, "engine.pass", 104.0, 60),
        _span(31, 30, "engine.admit", 104.0, 55, admitted=1, rids=[5]),
        _span(32, 31, "engine.prefill", 104.001, 50, bucket=64, rows=1),
        _span(33, 32, "engine.prefill.wait", 104.002, 46),
        # pass D: in the window, after the traced slot; its admit took none
        _span(40, None, "engine.pass", 106.0, 95),
        _span(42, 40, "engine.admit", 106.0, 1, admitted=0, rids=[]),
        _span(41, 40, "engine.decode.wait", 106.002, 90, pages_live=20,
              pages_table=200),
    ], tail='{"id": 99, "parent": nu')          # the writer is mid-line
    _write(new / "spans-w2.jsonl", [
        # another process, the same ids: host-only 10 - 4 = 6
        _span(10, None, "engine.pass", 103.5, 10),
        _span(13, 10, "engine.decode.wait", 103.501, 4, pages_live=40,
              pages_table=100),
    ], pid=8)
    old = tmp_path / "session-old"
    _write(old / "logs" / "spans-w1.jsonl", [
        _span(1, None, "request.queue", 50.0, 1000, rid=1),
        _span(2, None, "engine.pass", 50.0, 500),
        _span(3, 2, "engine.decode.wait", 50.1, 100, pages_live=5,
              pages_table=10),
    ])
    os.utime(old, (1.0, 1.0))
    return tmp_path


@pytest.mark.parametrize("name, by_hand", [
    ("queue_wait_ms", 6.0),              # median of 2, 6, 40
    ("loop_host_ms", 10.0),              # median of 10, 28, 6
    ("admit_host_ms", 14.5),             # median of 20, 9
    ("paged_live_share", 20.0),          # 100 x (10+30+20+40) / (100+100+200+100)
])
@pytest.mark.parametrize("twin", ["", ".closed"])
def test_reader_gives_the_number_worked_out_by_hand(sessions, name, by_hand,
                                                    twin):
    assert _reader(name + twin).read(OBS) == pytest.approx(by_hand)


@pytest.mark.parametrize("name", METRICS)
def test_reader_finds_nothing_where_there_is_nothing(sessions, tmp_path,
                                                     monkeypatch, name):
    read = _reader(name).read
    # No span in the interval (the older session's lie elsewhere).
    late = (200.0, 210.0)
    assert read({"window": late, "trace": {"window_mono_s": late}}) is None
    # An untraced run has no slot; the window's metrics do not need one.
    untraced = read({"window": WINDOW, "trace": None})
    assert (untraced is None) == (name in ("loop_host_ms", "admit_host_ms"))
    assert read({}) is None
    # No file at all: a program that writes no spans (the parent of PR 40).
    monkeypatch.setenv("RAY_TPU_TEMP_DIR", str(tmp_path / "nowhere"))
    assert read(OBS) is None


def test_an_older_session_is_read_only_for_its_own_interval(sessions):
    then = (40.0, 60.0)
    obs = {"window": then, "trace": {"window_mono_s": then}}
    assert _reader("queue_wait_ms").read(obs) == pytest.approx(1000.0)
    assert _reader("loop_host_ms").read(obs) == pytest.approx(400.0)
    assert _reader("paged_live_share").read(obs) == pytest.approx(50.0)
    # ... and plays no part in the newer one's (6.0, not a median with 1000).
    assert _reader("queue_wait_ms").read(OBS) == pytest.approx(6.0)


def test_a_file_last_written_before_the_interval_is_not_parsed(sessions):
    spans = _reader("program_spans")
    path = sessions / "session-new" / "logs" / "spans-w2.jsonl"
    # Its header puts monotonic 100 s at wall 5000 s; last written at wall
    # 5050 s is monotonic 150 s: too early for an interval from 200 s on,
    # in time for the window.
    os.utime(path, (5050.0, 5050.0))
    assert spans._records(str(path), int(200e9)) == []
    assert len(spans._records(str(path), int(100e9))) == 2


def test_the_eight_entries_are_the_last_and_name_cells_that_report_their_moves():
    entries = BENCH["per_layer"][-8:]
    assert [m["name"] for m in entries] == [
        n + twin for n in METRICS for twin in ("", ".closed")]
    reports = {w["name"]: {e["name"] for e in BENCH["end_to_end"]
                           if "workloads" not in e
                           or w["name"] in e["workloads"]}
               for w in BENCH["workloads"]}
    for m in entries:
        closed = m["name"].endswith(".closed")
        assert m["workloads"] == (CLOSED_CELLS if closed
                                  else ["mistral7b-serve-chat-open"])
        assert m["moves"] == ("batch_tokens_per_s" if closed
                              else "tpot_p90_ms")
        assert all(m["moves"] in reports[cell] for cell in m["workloads"])
        assert m["layer"] == "engine"
        assert m["source"] == ("program_counter" if "paged" in m["name"]
                               else "program_span")
        reader = _reader(m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == \
            (m["layer"], m["unit"], m["moves"])


def test_the_readers_read_what_the_engine_writes(tmp_path, monkeypatch):
    """The program's writer and the benchmark's reader, which share no
    code, agree on the file: spans recorded through `ray_tpu.util.tracing`
    in a session directory come back through `program_spans.session`."""
    import time

    from ray_tpu.util import tracing

    monkeypatch.setenv("RAY_TPU_TEMP_DIR", str(tmp_path))
    monkeypatch.setenv("RAY_TPU_SESSION_DIR", str(tmp_path / "session-live"))
    t0 = time.monotonic()
    with tracing.span("engine.pass"):
        with tracing.span("engine.decode.wait", active=1, steps=8,
                          pages_live=3, pages_table=12):
            time.sleep(0.002)
    tracing.record_span("request.queue", time.monotonic_ns() - 5_000_000,
                        rid=1, prompt_len=4, deferred=False)
    tracing.flush_spans()
    window = (t0 - 1.0, time.monotonic())
    obs = {"window": window, "trace": {"window_mono_s": window}}
    assert _reader("paged_live_share").read(obs) == pytest.approx(25.0)
    assert 5.0 <= _reader("queue_wait_ms").read(obs) < 50.0
    assert 0.0 < _reader("loop_host_ms").read(obs) < 2.0
    assert _reader("admit_host_ms").read(obs) is None
