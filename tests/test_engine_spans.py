"""Spans inside `LLMEngine._loop` (`ray_tpu/util/tracing.py`'s recorder): a
tiny engine serves three requests and what it did is read back from the
recorder, from the session's span file, from `ray_tpu timeline`'s rows and
from a `jax.profiler` trace's host plane.  And the chip's own row
(`chip.program`, PR 55): one span for every program the loop dispatched,
written by the watcher thread, held to its contract on what `served` left
and, where "behind the chunk on the chip" has to be a state and not a
race, under the doubles of `tests/llm_loop_doubles.py`.
"""

import glob
import json
import os
import time

import pytest

from ray_tpu.models.generate import SamplingParams
from ray_tpu.util import timeline, tracing
from tests.llm_loop_doubles import (  # noqa: F401 (fixtures)
    WAIT, _end, _held, _prompt, _with_its_first_chunk_held, engine, model)

LOOP_THREAD = "llm-engine"
WATCH_THREAD = "llm-engine-watch"
CHIP = "chip.program"
# Where each loop-scoped span may sit (PERF.md, section 3). The hand-off
# stands wherever the loop is about to wait: after a chunk's dispatch,
# before a prefill's fetch, or outside any pass before an idle wait.
PARENT_OF = {"engine.admit": {"engine.pass", "engine.commit"},
             "engine.prefill": {"engine.admit"},
             "engine.prefill.wait": {"engine.prefill", "engine.commit"},
             "engine.decode.build": {"engine.pass"},
             "engine.decode.upload": {"engine.decode.build"},
             "engine.decode.dispatch": {"engine.pass"},
             "engine.chip.wait": {"engine.pass", "engine.commit"},
             "engine.decode.wait": {"engine.pass"},
             "engine.walk": {"engine.pass"},
             "engine.commit": {"engine.pass"},
             "engine.handoff": {"engine.pass", "engine.prefill",
                                "engine.commit", None}}
# What three requests over two slots always leave.
ALWAYS = set(PARENT_OF) - {"engine.chip.wait", "engine.commit"}


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = LlamaConfig(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq_len=64)
    params = LlamaModel(cfg).init(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.int32))
    return cfg, params


def _engine(tiny):
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = tiny
    return LLMEngine(cfg, params, max_batch=2, max_len=64, page_size=16,
                     decode_chunk=4)


WRITER_WROTE: list = []      # the span files the writer thread made itself


@pytest.fixture(scope="module")
def served(tiny, tmp_path_factory):
    """Three requests over two slots of a new engine, inside a session
    directory of the module's own, served ONCE for the tests that read the
    spans: (handles, counters before, counters after, this run's spans,
    the session directory, where the run's spans were written out)."""
    from ray_tpu.models.generate import SamplingParams

    session = tmp_path_factory.mktemp("spans") / "session-test"
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("RAY_TPU_SESSION_DIR", str(session))
        eng = _engine(tiny)
        try:
            seen = {s["id"] for s in tracing.recent_spans()}
            before = eng.report_metrics()
            handles = [eng.submit(list(range(1, n)),
                                  SamplingParams(max_new_tokens=10))
                       for n in (4, 7, 21)]
            outs = [h.tokens() for h in handles]
            after = eng.report_metrics()
        finally:
            eng.shutdown()
        # The writer thread's own doing, about once a second; then what it
        # has not reached yet (outside a session spans are dropped).
        deadline = time.monotonic() + 2.0
        pattern = os.path.join(session, "logs", "spans-*.jsonl")
        while time.monotonic() < deadline and not glob.glob(pattern):
            time.sleep(0.05)
        WRITER_WROTE.extend(glob.glob(pattern))
        tracing.flush_spans()
    assert [len(o) for o in outs] == [10, 10, 10]
    spans = [s for s in tracing.recent_spans() if s["id"] not in seen]
    return handles, before, after, spans, session


def test_children_lie_inside_their_parents_on_one_thread(served):
    _, _, _, spans, _ = served
    by_id = {s["id"]: s for s in spans}
    loop = [s for s in spans if s["name"].startswith("engine.")]
    assert {s["name"] for s in loop} >= ALWAYS | {"engine.pass"}
    for s in loop:
        assert s["thread"] == LOOP_THREAD
        if s["name"] in ("engine.pass", "engine.idle"):
            assert s["parent"] is None      # idle lies outside any pass
            continue
        if s["parent"] is None:
            assert None in PARENT_OF[s["name"]]
            continue
        parent = by_id[s["parent"]]
        assert parent["name"] in PARENT_OF[s["name"]]
        assert parent["tid"] == s["tid"]
        assert parent["t0_ns"] <= s["t0_ns"]
        assert s["t0_ns"] + s["dur_ns"] <= parent["t0_ns"] + parent["dur_ns"]


def test_a_pass_is_its_host_only_time_and_its_waits(served):
    _, _, _, spans, _ = served
    children: dict = {}
    for s in spans:
        children.setdefault(s["parent"], []).append(s)

    def descendants(s):
        for c in children.get(s["id"], []):
            yield c
            yield from descendants(c)

    passes = [s for s in spans if s["name"] == "engine.pass"]
    assert passes
    for p in passes:
        below = list(descendants(p))
        waits = sum(d["dur_ns"] for d in below if d["name"].endswith(".wait"))
        phases = sum(d["dur_ns"] for d in children.get(p["id"], []))
        # The waits are part of the pass, and the host-only rest is made
        # of the phases outside them plus what lies between two phases.
        assert 0 <= waits <= phases <= p["dur_ns"]
        host_only = p["dur_ns"] - waits
        assert host_only + waits == p["dur_ns"] and host_only > 0
        # Every pass admitted or dispatched.
        assert {d["name"] for d in below} & {"engine.admit",
                                            "engine.decode.wait"}


def test_a_requests_spans_share_its_rid_and_its_admit_names_it(served):
    handles, _, _, spans, _ = served
    rids = [h.rid for h in handles]
    assert len(set(rids)) == 3
    for h in handles:
        mine = {s["name"]: s for s in spans if s.get("rid") == h.rid}
        assert set(mine) == {"request.queue", "request.first_token"}
        queue, first = mine["request.queue"], mine["request.first_token"]
        # Both begin at `submit`; the first token follows the admission.
        assert queue["t0_ns"] == first["t0_ns"] == h._submit_ns
        assert queue["dur_ns"] <= first["dur_ns"]
        assert queue["attrs"] == {"prompt_len": h.prompt_len,
                                  "deferred": False}
        took = [s for s in spans if s["name"] == "engine.admit"
                and h.rid in s["attrs"]["rids"]]
        assert len(took) == 1
        admit = took[0]
        assert admit["attrs"]["admitted"] == len(admit["attrs"]["rids"])
        # The wait ends inside the admission that ended it.
        ended = queue["t0_ns"] + queue["dur_ns"]
        assert admit["t0_ns"] <= ended <= admit["t0_ns"] + admit["dur_ns"]
    prefills = [s for s in spans if s["name"] == "engine.prefill"]
    assert sum(s["attrs"]["rows"] for s in prefills) == 3
    assert all(s["attrs"]["bucket"] in (16, 32) for s in prefills)


def test_a_prefill_says_how_many_positions_its_program_computes(
        served, tiny, monkeypatch):
    """`computed` on `engine.prefill`: every row's whole bucket where the
    bucket is no longer than the family's block (every program of this
    engine: the rule's block is hundreds of positions), else the blocks
    some row of the group reaches, for every row."""
    from ray_tpu.models.generate import SamplingParams
    from ray_tpu.ops import prompt_blocks

    _, _, _, spans, _ = served
    prefills = [s["attrs"] for s in spans if s["name"] == "engine.prefill"]
    assert prefills and all(a["computed"] == a["bucket"] * a["rows"]
                            for a in prefills)
    monkeypatch.setattr(prompt_blocks, "rows_of_a_block", lambda *a, **k: 16)
    eng = _engine(tiny)
    try:
        seen = {s["id"] for s in tracing.recent_spans()}
        for n in (40, 10):
            assert len(eng.submit(list(range(1, n + 1)), SamplingParams(
                max_new_tokens=2)).tokens()) == 2
    finally:
        eng.shutdown()
    got = [(s["attrs"]["bucket"], s["attrs"]["rows"], s["attrs"]["computed"])
           for s in tracing.recent_spans()
           if s["name"] == "engine.prefill" and s["id"] not in seen]
    # 40 tokens reach three blocks of 16 of a bucket of 64; a bucket of 16
    # is one block
    assert got == [(64, 1, 48), (16, 1, 16)]


def test_page_counts_on_the_spans_are_the_engines_counters(served):
    _, before, after, spans, _ = served
    chunks = [s["attrs"] for s in spans if s["name"] == "engine.decode.wait"]
    assert chunks
    for counter, attr in (("paged_pages_live", "pages_live"),
                          ("paged_pages_table", "pages_table"),
                          ("state_slot_steps", "steps")):
        assert sum(c[attr] for c in chunks) == after[counter] - before[counter]
    walks = [s["attrs"] for s in spans if s["name"] == "engine.walk"]
    # Each request's first token comes from its prefill, the rest from walks.
    assert sum(w["emitted"] for w in walks) == 3 * 10 - 3
    assert sum(w["finished"] for w in walks) == 3


def test_a_build_says_what_it_uploaded_and_holds_the_transfers(served):
    _, before, after, spans, _ = served
    builds = [s for s in spans if s["name"] == "engine.decode.build"]
    uploads = {s["parent"] for s in spans
               if s["name"] == "engine.decode.upload"}
    assert len(builds) == after["decode_passes"] - before["decode_passes"]
    # A dense model: a pass that follows no admission, finish or park
    # sends nothing, so it has no `upload` child; the others have one.
    assert {b["id"] for b in builds if b["attrs"]["uploaded"]} == uploads
    clean = [b for b in builds if not b["attrs"]["uploaded"]]
    assert len(clean) == (after["decode_passes_clean"]
                          - before["decode_passes_clean"])
    # Ten tokens a request in chunks of four: each stream has a chunk in
    # its middle that nothing disturbs; the first pass sends everything.
    assert clean and len(clean) < len(builds)
    assert builds[0]["attrs"]["uploaded"] == 8


def test_timeline_draws_the_span_files_as_rows(served):
    _, _, _, spans, session = served
    tracing.flush_spans()
    rows = timeline.span_trace_events(str(session))
    assert {r["cat"] for r in rows} == {"span"} and \
        {r["ph"] for r in rows} == {"X"}
    mine = [r for r in rows if r["args"].get("id") in
            {s["id"] for s in spans}]
    assert len(mine) == len(spans)
    # One row a process and thread, the chip's beside the loop's;
    # wall-clock microseconds.
    assert {(r["pid"], r["tid"]) for r in mine} == \
        {(f"spans:pid{os.getpid()}", LOOP_THREAD),
         (f"spans:pid{os.getpid()}", WATCH_THREAD)}
    assert {r["name"] for r in mine if r["tid"] == WATCH_THREAD} == {CHIP}
    wait = next(r for r in mine if r["name"] == "engine.decode.wait")
    assert abs(wait["ts"] / 1e6 - time.time()) < 600
    assert wait["args"]["pages_table"] > 0


# ---- the chip's row ---------------------------------------------------------


def _programs(spans):
    return sorted((s for s in spans if s["name"] == CHIP),
                  key=lambda s: s["attrs"]["seq"])


def test_every_dispatched_program_has_one_span_in_the_chips_order(served):
    _, _, _, spans, _ = served
    programs = _programs(spans)
    dispatches = [s for s in spans if s["name"] == "engine.decode.dispatch"]
    prefills = [s for s in spans if s["name"] == "engine.prefill"]
    by_kind = {k: [p for p in programs if p["attrs"]["kind"] == k]
               for k in ("decode", "prefill")}
    assert len(by_kind["decode"]) == len(dispatches) > 0
    assert len(by_kind["prefill"]) == len(prefills) > 0
    assert len(programs) == len(dispatches) + len(prefills)
    # Numbered as dispatched, and written in that order by ONE thread.
    seqs = [p["attrs"]["seq"] for p in programs]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    assert [p["id"] for p in programs] == sorted(p["id"] for p in programs)
    for before, p in zip([None] + programs, programs):
        a = p["attrs"]
        assert p["thread"] == WATCH_THREAD and p["parent"] is None
        assert a["seen_by"] in ("watch", "fetch") and a["late_ns"] >= 0
        assert (a["late_ns"] == 0) or a["seen_by"] == "fetch"
        assert p["dur_ns"] >= 0 and a["starved_ns"] >= 0
        # It starts when it was queued or when the one before it ended,
        # whichever is later; the rest of the gap is the chip's hunger.
        if before is not None:
            assert p["t0_ns"] == max(a["queued_ns"], _end(before))
            assert a["starved_ns"] == max(0, a["queued_ns"] - _end(before))
            assert _end(before) <= p["t0_ns"]           # none overlaps
    # `queued_ns` is the loop's stamp at the END of the program's dispatch
    # (of a prefill group's three, the first's).
    for p, d in zip(by_kind["decode"], dispatches):
        assert d["t0_ns"] <= p["attrs"]["queued_ns"] <= _end(d)


def test_a_chunks_span_ends_no_later_than_its_fetch(served):
    _, _, _, spans, _ = served
    chunks = [p for p in _programs(spans) if p["attrs"]["kind"] == "decode"]
    waits = [s for s in spans if s["name"] == "engine.decode.wait"]
    assert len(chunks) == len(waits)
    for p, w in zip(chunks, waits):
        assert _end(p) <= _end(w)
        # ... and says of the chunk what its fetch says
        assert (p["attrs"]["active"], p["attrs"]["steps"]) == \
            (w["attrs"]["active"], w["attrs"]["steps"])
    fetches = [s for s in spans if s["name"] == "engine.prefill.wait"]
    groups = [p for p in _programs(spans) if p["attrs"]["kind"] == "prefill"]
    for p, w in zip(groups, fetches):
        assert _end(p) <= _end(w)


def test_a_prefills_span_names_its_group_as_engine_prefill_does(served):
    handles, _, _, spans, _ = served
    by_id = {s["id"]: s for s in spans}
    lengths = {h.rid: h.prompt_len for h in handles}
    groups = [p for p in _programs(spans) if p["attrs"]["kind"] == "prefill"]
    prefills = [s for s in spans if s["name"] == "engine.prefill"]
    assert sorted(r for p in groups for r in p["attrs"]["rids"]) == \
        sorted(lengths)
    for p, host in zip(groups, prefills):        # both in dispatch order
        a = p["attrs"]
        assert {k: a[k] for k in ("bucket", "rows", "width", "computed")} \
            == host["attrs"]
        assert len(a["rids"]) == a["rows"]
        assert set(a["rids"]) <= set(by_id[host["parent"]]["attrs"]["rids"])
        assert a["prompt_tokens"] == sum(lengths[r] for r in a["rids"])
        # queued inside its `engine.prefill`: when its first dispatch (of
        # three) had returned
        assert host["t0_ns"] <= a["queued_ns"] <= _end(host)


def test_a_prefill_queued_under_a_chunk_starts_at_the_chunks_end(
        model, engine):
    eng, hold = _held(engine)
    first_p, second_p = _prompt(71, 9), _prompt(72, 23)
    first, stream, head = _with_its_first_chunk_held(eng, hold, first_p, 30)
    second = eng.submit(second_p, SamplingParams(max_new_tokens=9))
    assert hold.prefilled.wait(WAIT) and hold.held()
    hold.release()
    assert model.is_greedy(second_p, second.tokens())
    assert model.is_greedy(first_p, head + list(stream))
    eng.shutdown()                      # the watcher has written them all
    programs = _programs(hold.spans())
    behind, = [p for p in programs
               if p["attrs"].get("rids") == [second.rid]]
    chunk = programs[programs.index(behind) - 1]
    admit, = [s for s in hold.spans("engine.admit")
              if second.rid in s["attrs"]["rids"]]
    assert admit["attrs"]["under_chunk"] is True
    assert chunk["attrs"]["kind"] == "decode"
    # Queued while the chunk was on the chip: the chip went from one to
    # the other, and was never without a program between them.
    assert behind["attrs"]["queued_ns"] < _end(chunk) == behind["t0_ns"]
    assert behind["attrs"]["starved_ns"] == 0
    assert behind["attrs"]["prompt_tokens"] == len(second_p)


def test_the_loop_wakes_on_its_own_programs_end_among_those_watched(
        model, engine):
    """The watcher sees every program end; the loop, waiting behind a
    prefill in flight, is not let go by the end of the chunk before it."""
    eng, hold = _held(engine)
    prompts = [_prompt(73, 9), _prompt(74, 23), _prompt(75, 14)]
    eng._prefill_bytes.update({(1, 16): 40, (1, 32): 60, (3, 32): 100})
    first, stream, head = _with_its_first_chunk_held(
        eng, hold, prompts[0], 30)
    hold.hold_prefills = True
    second = eng.submit(prompts[1], SamplingParams(max_new_tokens=9))
    assert hold.prefilled.wait(WAIT)
    hold.hold_prefills = False
    hold.gates[0].set()                     # the chunk is done
    held_prefill, = hold.prefills_held
    assert held_prefill.watched.wait(WAIT)  # the watcher is at the prefill
    hold.prefilled.clear()
    third = eng.submit(prompts[2], SamplingParams(max_new_tokens=7))
    # Admitted behind the prefill, which is still on the chip: the loop
    # was waiting for THAT program, with the chunk's end long announced.
    assert hold.prefilled.wait(WAIT) and hold.held()
    hold.release()
    for p, out in zip(prompts, (head + list(stream), second.tokens(),
                                third.tokens())):
        assert model.is_greedy(p, out)
    eng.shutdown()
    programs = _programs(hold.spans())
    of = {tuple(p["attrs"].get("rids", ())): p for p in programs}
    held_span = of[(second.rid,)]
    chunk = programs[programs.index(held_span) - 1]
    admit, = [s for s in hold.spans("engine.admit")
              if third.rid in s["attrs"]["rids"]]
    assert _end(chunk) <= admit["t0_ns"] <= _end(admit) <= _end(held_span)
    assert of[(third.rid,)]["attrs"]["seq"] == held_span["attrs"]["seq"] + 1
    assert eng._chip_done == programs[-1]["attrs"]["seq"]


def test_a_recorded_span_takes_the_end_it_is_given():
    t0 = time.monotonic_ns()
    tracing.record_span("given-end", t0 - 5000, t1_ns=t0 - 2000, a=1)
    tracing.record_span("ends-now", t0 - 5000)
    given, now = tracing.recent_spans()[-2:]
    assert (given["name"], given["t0_ns"], given["dur_ns"]) == \
        ("given-end", t0 - 5000, 3000)
    assert given["attrs"] == {"a": 1} and given["parent"] is None
    assert now["name"] == "ends-now" and now["dur_ns"] >= 5000


def test_timeline_chip_prints_the_chips_ledger_of_a_session(
        served, capsys, monkeypatch):
    _, _, _, spans, session = served
    tracing.flush_spans()
    from ray_tpu import scripts

    monkeypatch.setattr("sys.argv", ["ray_tpu", "timeline", "--chip",
                                     str(session)])
    with pytest.raises(SystemExit) as done:
        scripts.main()
    assert done.value.code == 0
    out = capsys.readouterr().out
    assert out == timeline.chip_report(str(session)) + "\n"
    programs = _programs(spans)
    assert f"{len(programs)} programs" in out
    lines = out.splitlines()
    # The four parts ISSUE 55 names, and the programs that ran longest
    # over their like; each a heading and its rows.
    parts = [i for i, line in enumerate(lines) if line.endswith(":")]
    assert [" ".join(lines[i].split()[:2]) for i in parts] == [
        "chip seconds", "starved seconds", "longest starved", "late_ns (the",
        "longest programs"]
    kinds = {line.split()[0]: int(line.split()[3])
             for line in lines[parts[0] + 1:parts[1]]}
    assert kinds == {k: sum(p["attrs"]["kind"] == k for p in programs)
                     for k in ("decode", "prefill")}
    # Every starved nanosecond lies under a label: a span of the loop's,
    # the deepest over it, or none.
    starved = [p["attrs"]["starved_ns"] for p in programs]
    total, intervals = lines[parts[1]].split("(")[1].split(" s in ")
    assert float(total) == pytest.approx(sum(starved) / 1e9, abs=1e-3)
    assert int(intervals.split()[0]) == sum(ns > 0 for ns in starved)
    under = {line.split("  ")[1].strip(): float(line.split()[-2])
             for line in lines[parts[1] + 1:parts[2]]}
    # (a label under half a millisecond is left out, the rest rounded)
    assert 0 < sum(under.values()) <= float(total) + 1e-3 * len(under)
    assert all(k == timeline.NO_SPAN or k.startswith("engine.")
               for k in under)
    longest = lines[parts[2] + 1:parts[3]]
    assert 1 <= len(longest) <= timeline.LONGEST_SHOWN
    assert all(" under " in line and " before " in line for line in longest)
    seen = {line.split()[0] for line in lines[parts[3] + 1:parts[4]]}
    assert seen == {p["attrs"]["seen_by"] for p in programs}
    slowest = lines[parts[4] + 1:]
    assert 1 <= len(slowest) <= timeline.LONGEST_SHOWN
    assert all(" against " in line and " seq " in line for line in slowest)
    # Clipped to an interval that holds nothing of it: nothing to say.
    assert "no chip.program" in timeline.chip_report(
        str(session), interval=(0.0, 1.0))
    assert "no chip.program" in timeline.chip_report(str(session / "none"))


@pytest.mark.parametrize("attrs, shape", [
    ({"kind": "decode"}, "decode"),
    ({"kind": "prefill", "computed": 4096},
     "prefill of 4096 positions"),
    ({"kind": "prefill", "computed": 4096, "blocks": 8},
     "prefill of 4096 positions in 8 blocks")],
    ids=["a-chunk", "one-program", "a-block-a-dispatch"])
def test_programs_are_compared_with_their_like(attrs, shape):
    """What `timeline --chip` holds a program's length against: a chunk
    against chunks, a prefill against those that computed as many positions
    (a family that dispatches a prompt a block at a time: in as many
    blocks)."""
    assert timeline._shape({"attrs": attrs}) == shape


def test_the_span_file_appears_in_the_session_and_stays_under_its_cap(
        served, monkeypatch):
    """(The last reader of `served`: it turns the session's files over.)"""
    _, _, _, spans, session = served
    monkeypatch.setenv("RAY_TPU_SESSION_DIR", str(session))
    pattern = os.path.join(session, "logs", "spans-*.jsonl")
    assert len(WRITER_WROTE) == 1 and glob.glob(pattern) == WRITER_WROTE
    tracing.flush_spans()
    (header, written), = tracing.read_span_files(str(session))
    assert header["pid"] == os.getpid()
    # The header pairs the two clocks at one instant.
    assert abs((time.time() - header["time_s"])
               - (time.monotonic_ns() - header["mono_ns"]) / 1e9) < 0.5
    by_id = {s["id"]: s for s in written}
    for s in spans:
        assert by_id[s["id"]] == json.loads(json.dumps(s))
    # A flight recorder: two files of a fixed size, the older dropped.
    cap = 4096
    monkeypatch.setattr(tracing, "SPAN_FILE_BYTES", cap)
    for round_ in range(6):
        for i in range(20):
            with tracing.span("filler", round=round_, i=i):
                pass
        tracing.flush_spans()
        files = sorted(glob.glob(pattern + "*"))
        assert len(files) <= 2
        # (The file that was there before the cap shrank is the older one.)
        assert os.path.getsize(files[0]) <= cap
    assert len(files) == 2 and files[1].endswith(".1")
    newest = tracing.read_span_files(str(session))[-1][1]
    assert newest[-1]["attrs"] == {"round": 5, "i": 19}


def test_spans_lie_on_the_profilers_host_plane(tiny, tmp_path):
    import jax
    from ray_tpu.models.generate import SamplingParams

    eng = _engine(tiny)
    try:
        eng.generate([1, 2, 3], SamplingParams(max_new_tokens=2))  # compile
        jax.profiler.start_trace(str(tmp_path))
        try:
            eng.generate([1, 2, 3, 4], SamplingParams(max_new_tokens=9))
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.shutdown()
    path, = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    names = {}
    for plane in data.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("engine."):
                    names.setdefault(event.name, []).append(
                        dict(event.stats))
    assert {"engine.pass", "engine.decode.build", "engine.decode.dispatch",
            "engine.decode.wait", "engine.walk"} <= set(names)
    # Scalar attrs ride on the annotation: a chunk's own page counts.
    assert all(int(st["pages_table"]) > 0
               for st in names["engine.decode.wait"])


def test_an_unclosed_child_does_not_become_later_spans_parent():
    outer = tracing.span("outer").begin()
    tracing.span("left-open").begin()      # an exception passed its end()
    outer.end()
    with tracing.span("next") as nxt:
        pass
    assert nxt.parent == 0
    assert [s["name"] for s in tracing.recent_spans()[-2:]] == ["outer",
                                                                "next"]


def test_timeline_of_a_session_that_served_shows_the_engines_rows(
        ray_start_regular, tmp_path):
    """An engine inside a worker of a real session (forked from the
    zygote, its span file named by its worker id), then `ray_tpu
    timeline`'s dump from the driver."""
    import ray_tpu

    @ray_tpu.remote
    class Replica:
        def serve_one(self):
            import jax
            import jax.numpy as jnp

            from ray_tpu.models.generate import SamplingParams
            from ray_tpu.models.llama import LlamaConfig, LlamaModel
            from ray_tpu.serve.llm import LLMEngine
            from ray_tpu.util import tracing as worker_tracing

            cfg = LlamaConfig(vocab_size=128, d_model=64, n_layers=1,
                              n_heads=4, n_kv_heads=2, d_ff=128,
                              max_seq_len=64)
            params = LlamaModel(cfg).init(jax.random.PRNGKey(0),
                                          jnp.zeros((1, 8), jnp.int32))
            eng = LLMEngine(cfg, params, max_batch=2, max_len=64,
                            page_size=16, decode_chunk=4)
            try:
                out = eng.generate([1, 2, 3], SamplingParams(max_new_tokens=6))
            finally:
                eng.shutdown()
            worker_tracing.flush_spans()
            return len(out), os.environ["RAY_TPU_WORKER_ID"][:12]

    n, worker = ray_tpu.get(Replica.remote().serve_one.remote(), timeout=170)
    assert n == 6
    path = timeline.dump_timeline(str(tmp_path / "timeline.json"))
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    rows = [e for e in events if e["cat"] == "span"]
    assert {"engine.pass", "engine.decode.wait", "request.queue"} <= \
        {e["name"] for e in rows if e["pid"] == f"spans:{worker}"}
    assert any(e["cat"] == "task" for e in events)  # beside the task rows
