"""Chrome-trace timeline from GCS task events.

Parity: reference `ray timeline` (scripts.py:2459) which dumps per-worker
profile events (core_worker/profile_event.cc → task_event_buffer.h) as a
chrome://tracing JSON. Here the GCS task-event table provides the full
lifecycle ladder: one "X" complete event per task execution on (node,
worker) rows, plus per-STAGE sub-spans (queue, lease negotiation,
dispatch, arg fetch) on dedicated "stage:<name>" rows so where a slow
task spent its pre-execution time is visible at a glance. The session's
program spans (`util/tracing.py`: what an engine's loop was doing, a
request's waits) join it as rows of their own, one a process and thread;
the chip's own row (`chip.program`, one span a program an engine
dispatched) is also summed up as text (`chip_report`).
"""

from __future__ import annotations

import json
import os
import statistics
from bisect import bisect_right
from collections import defaultdict

import ray_tpu
from ray_tpu._private.api_internal import get_core_worker
from ray_tpu.util import tracing

# Pre-execution ladder segments rendered as their own rows (everything
# up to and including RUNNING — one shared definition with the state
# API); the RUNNING→FINISHED span stays the per-worker execution row.
from ray_tpu.util.state import LIFECYCLE_STAGES

_STAGE_LADDER = LIFECYCLE_STAGES[:LIFECYCLE_STAGES.index("RUNNING") + 1]
_STAGE_NAMES = {"LEASE_REQUESTED": "queue", "LEASE_GRANTED": "lease",
                "DISPATCHED": "dispatch", "ARGS_FETCHED": "args_fetch",
                "RUNNING": "startup"}


def _stage_rows(task_stamps: dict[str, dict[str, dict]]) -> list[dict]:
    """Per-stage sub-spans: for each task, an 'X' between each pair of
    consecutive recorded ladder stamps, on a row per stage."""
    trace = []
    for tid, stamps in task_stamps.items():
        present = [s for s in _STAGE_LADDER if s in stamps]
        for frm, to in zip(present, present[1:]):
            e0, e1 = stamps[frm], stamps[to]
            trace.append({
                "name": e0.get("name", tid),
                "cat": "stage",
                "ph": "X",
                "ts": e0["ts"] * 1e6,
                "dur": max(0.0, (e1["ts"] - e0["ts"]) * 1e6),
                "pid": "lifecycle",
                "tid": f"stage:{_STAGE_NAMES[to]}",
                "args": {"task_id": tid, "from": frm, "to": to},
            })
    return trace


def build_trace_events(events: list[dict]) -> list[dict]:
    """Pair per-task state transitions into chrome trace 'X' events."""
    starts: dict[str, dict] = {}
    trace: list[dict] = []
    task_stamps: dict[str, dict[str, dict]] = {}
    for e in sorted(events, key=lambda e: e.get("ts", 0.0)):
        state = e.get("state")
        tid = e.get("task_id")
        if state in _STAGE_LADDER:
            task_stamps.setdefault(tid, {}).setdefault(state, e)
        if state == "RUNNING":
            starts[tid] = e
        elif state in ("FINISHED", "FAILED") and tid in starts:
            s = starts.pop(tid)
            trace.append({
                "name": s.get("name", tid),
                "cat": "task",
                "ph": "X",
                "ts": s["ts"] * 1e6,
                "dur": max(0.0, (e["ts"] - s["ts"]) * 1e6),
                "pid": s.get("node_id", "node")[:8],
                "tid": s.get("worker_id", "worker")[:8],
                "args": {"task_id": tid, "state": state,
                         "job_id": s.get("job_id", "")},
            })
    # Unfinished tasks appear as instant events.
    for tid, s in starts.items():
        trace.append({"name": s.get("name", tid), "cat": "task", "ph": "i",
                      "ts": s["ts"] * 1e6, "pid": s.get("node_id", "n")[:8],
                      "tid": s.get("worker_id", "w")[:8], "s": "t",
                      "args": {"task_id": tid, "state": "RUNNING"}})
    trace.extend(_stage_rows(task_stamps))
    return trace


def span_trace_events(session_dir: str) -> list[dict]:
    """The session's span files as chrome trace 'X' events (category
    `span`), on the task events' clock: a file's header pairs a wall-clock
    reading with the monotonic one its spans are stamped in."""
    trace = []
    for header, spans in tracing.read_span_files(session_dir):
        if not header:
            continue
        for s in spans:
            wall_s = header["time_s"] + (s["t0_ns"] - header["mono_ns"]) / 1e9
            args = {"id": s["id"], "parent": s["parent"],
                    **s.get("attrs", {})}
            if "rid" in s:
                args["rid"] = s["rid"]
            trace.append({"name": s["name"], "cat": "span", "ph": "X",
                          "ts": wall_s * 1e6, "dur": s["dur_ns"] / 1e3,
                          "pid": f"spans:{header['label']}",
                          "tid": s["thread"], "args": args})
    return trace


def dump_timeline(path: str = "/tmp/ray_tpu_timeline.json",
                  limit: int = 100000, session_dir: str | None = None) -> str:
    """`session_dir`: whose span files to merge in; this process's own
    session where None (a driver that started the cluster, or a worker)."""
    cw = get_core_worker()
    events = cw._run(cw.gcs.call("ListTaskEvents", {"limit": limit}))["events"]
    trace = build_trace_events(events)
    if session_dir is None:
        node = ray_tpu._runtime_node
        session_dir = node.session_dir if node is not None \
            else os.environ.get("RAY_TPU_SESSION_DIR")
    if session_dir:
        trace.extend(span_trace_events(session_dir))
    with open(path, "w") as f:
        json.dump({"traceEvents": trace, "displayTimeUnit": "ms"}, f)
    return path


# ---------------------------------------------------------------------------
# The chip's ledger, as text
# ---------------------------------------------------------------------------

CHIP_SPAN = "chip.program"
NO_SPAN = "(no span)"
LONGEST_SHOWN = 5


def _label(span: dict) -> str:
    why = span.get("attrs", {}).get("why")
    return f"{span['name']}:{why}" if why else span["name"]


def _end(span: dict) -> int:
    return span["t0_ns"] + span["dur_ns"]


class _LoopRows:
    """The spans of the threads that dispatch (every thread that recorded
    an `engine.*` span), as a forest: which of them is the DEEPEST over an
    instant is what that thread was doing then."""

    def __init__(self, spans: list):
        loop = [s for s in spans if s["name"].startswith("engine.")]
        self._children: dict = defaultdict(list)
        for s in loop:
            if s["parent"]:
                self._children[s["parent"]].append(s)
        self._tops = sorted((s for s in loop if not s["parent"]),
                            key=lambda s: s["t0_ns"])
        self._starts = [s["t0_ns"] for s in self._tops]

    def split(self, t0: int, t1: int) -> dict:
        """{label: ns} of [t0, t1) by the deepest span over each instant."""
        out: dict = defaultdict(int)
        covered = 0
        # (top-level spans of one thread do not overlap: a pass, an idle)
        i = max(0, bisect_right(self._starts, t0) - 1)
        while i < len(self._tops) and self._tops[i]["t0_ns"] < t1:
            covered += self._into(self._tops[i], t0, t1, out)
            i += 1
        if t1 - t0 > covered:
            out[NO_SPAN] += t1 - t0 - covered
        return out

    def _into(self, span: dict, t0: int, t1: int, out: dict) -> int:
        lo, hi = max(t0, span["t0_ns"]), min(t1, _end(span))
        if lo >= hi:
            return 0
        below = sum(self._into(c, lo, hi, out)
                    for c in self._children.get(span["id"], ()))
        out[_label(span)] += hi - lo - below
        return hi - lo


def _shape(program: dict) -> str:
    """What makes two programs comparable in length."""
    a = program["attrs"]
    if a["kind"] != "prefill":
        return a["kind"]
    # (`blocks`: a family that dispatches a prompt a block at a time)
    return f"prefill of {a['computed']} positions" + (
        f" in {a['blocks']} blocks" if "blocks" in a else "")


def _quantile(values: list, q: float) -> float:
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, int(q * len(ordered)))]


def _named(program: dict) -> str:
    a = program["attrs"]
    return f"{a['kind']} seq {a['seq']}" + (
        f" rids {a['rids']}" if "rids" in a else "")


def _seconds_by_kind(programs: list, lo: int, hi: int) -> list:
    by_kind: dict = defaultdict(lambda: [0, 0])
    for p in programs:
        on = by_kind[p["attrs"]["kind"]]
        on[0] += 1
        on[1] += max(0, min(hi, _end(p)) - max(lo, p["t0_ns"]))
    return ["chip seconds by kind:"] + [
        f"  {kind:<8} {ns / 1e9:10.3f} s  {n:6d} programs"
        for kind, (n, ns) in sorted(by_kind.items())]


def _starved(programs: list, rows: _LoopRows, lo: int, hi: int) -> list:
    starved, by_span = [], defaultdict(int)
    for p in programs:
        t0 = max(lo, p["t0_ns"] - p["attrs"]["starved_ns"])
        t1 = min(hi, p["t0_ns"])
        if t0 >= t1:
            continue
        split = rows.split(t0, t1)
        for label, ns in split.items():
            by_span[label] += ns
        starved.append((t1 - t0, max(split, key=split.get), p))
    lines = [f"starved seconds by what the loop was doing "
             f"({sum(by_span.values()) / 1e9:.3f} s in {len(starved)} "
             "intervals):"]
    lines += [f"  {label:<28} {ns / 1e9:10.3f} s"
              for label, ns in sorted(by_span.items(), key=lambda kv: -kv[1])
              if ns >= 500_000]     # (what would print as 0.000 is left out)
    lines.append("longest starved intervals:")
    lines += [f"  {ns / 1e6:10.3f} ms  under {label:<24} before {_named(p)}"
              for ns, label, p in sorted(starved, key=lambda x: -x[0])[
                  :LONGEST_SHOWN]]
    return lines


def _lateness(programs: list) -> list:
    late: dict = defaultdict(list)
    for p in programs:
        late[p["attrs"]["seen_by"]].append(p["attrs"]["late_ns"])
    return ["late_ns (the watcher's reading after the end) by seen_by: "
            "median / 99th percentile, us:"] + [
        f"  {who:<6} {len(values):6d} programs  "
        f"{statistics.median(values) / 1e3:10.1f} / "
        f"{_quantile(values, 0.99) / 1e3:10.1f}"
        for who, values in sorted(late.items())]


def _over_their_like(programs: list, rows: _LoopRows) -> list:
    like: dict = defaultdict(list)
    for p in programs:
        like[_shape(p)].append(p)
    over = []
    for shape, same in like.items():
        median = statistics.median(p["dur_ns"] for p in same)
        over += [(p["dur_ns"] - median, median, shape, p) for p in same]
    lines = ["longest programs over the median of their shape:"]
    for excess, median, shape, p in sorted(over, key=lambda x: -x[0])[
            :LONGEST_SHOWN]:
        # what the loop was doing over the span's last `excess` ns
        split = rows.split(int(_end(p) - excess), _end(p)) \
            if excess > 0 else {NO_SPAN: 0}
        lines.append(
            f"  {excess / 1e6:+10.3f} ms  ({p['dur_ns'] / 1e6:.3f} against "
            f"{median / 1e6:.3f} of {len(like[shape])} {shape})  under "
            f"{max(split, key=split.get):<20} {_named(p)}")
    return lines


def chip_report(session_dir: str, interval: tuple | None = None) -> str:
    """What the `chip.program` spans of a session's files say, a process
    at a time: chip seconds by `kind`; starved seconds (the chip had
    nothing queued) by the deepest dispatching-thread span over each
    starved instant; the longest starved intervals, each with the span
    that covers most of it and the program that ended it; how late the
    watcher read a program's end where the loop's fetch read it first
    (`late_ns`, by `seen_by`); and the programs that ran longest over the
    median of their shape (a span ends when the HOST hears of the end: one
    far over its like is a program the host heard of late, the chip idle
    inside it). `interval`: seconds of `time.monotonic()` to clip to (a
    benchmark's window, warm-up left out: PERF.md section 5's tables); the
    whole of the files where None."""
    lo, hi = (int(t * 1e9) for t in interval) if interval \
        else (-(1 << 63), 1 << 63)
    lines = []
    for header, spans in tracing.read_span_files(session_dir):
        programs = sorted(
            (s for s in spans if s["name"] == CHIP_SPAN
             and s["t0_ns"] - s["attrs"]["starved_ns"] < hi
             and _end(s) > lo), key=lambda s: s["attrs"]["seq"])
        if not programs:
            continue
        rows = _LoopRows(spans)
        first = max(lo, min(p["t0_ns"] for p in programs))
        last = min(hi, max(_end(p) for p in programs))
        lines.append(f"== {header.get('label', '?')} (pid "
                     f"{header.get('pid', '?')}): {len(programs)} programs "
                     f"over {(last - first) / 1e9:.3f} s")
        lines += _seconds_by_kind(programs, lo, hi)
        lines += _starved(programs, rows, lo, hi)
        lines += _lateness(programs)
        lines += _over_their_like(programs, rows)
    return "\n".join(lines) if lines else \
        f"no {CHIP_SPAN} span under {session_dir}"
