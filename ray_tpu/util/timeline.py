"""Chrome-trace timeline from GCS task events.

Parity: reference `ray timeline` (scripts.py:2459) which dumps per-worker
profile events (core_worker/profile_event.cc → task_event_buffer.h) as a
chrome://tracing JSON. Here the GCS task-event table provides the full
lifecycle ladder: one "X" complete event per task execution on (node,
worker) rows, plus per-STAGE sub-spans (queue, lease negotiation,
dispatch, arg fetch) on dedicated "stage:<name>" rows so where a slow
task spent its pre-execution time is visible at a glance. The session's
program spans (`util/tracing.py`: what an engine's loop was doing, a
request's waits) join it as rows of their own, one a process and thread.
"""

from __future__ import annotations

import json
import os

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
