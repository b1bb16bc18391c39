"""Distributed tracing: spans around task/actor submission and execution,
W3C trace context propagated inside the TaskSpec.

Parity: reference python/ray/util/tracing/tracing_helper.py:34-181
(_tracing_task_invocation wraps submission, _inject_tracing_into_function
wraps execution; context rides in the TaskSpec).

Two layers:
- Built-in propagation (always available): W3C `traceparent` strings are
  generated/parsed internally and carried in TaskSpec.trace_ctx, so a task
  anywhere in the cluster can see the root trace id.
- OpenTelemetry export (optional): when an OTel SDK TracerProvider is
  passed to `setup_tracing` (or installed globally), real spans are
  emitted through it as well — the standard API/SDK split: this library
  speaks the API, the application provides the SDK/exporter.

Enable with `setup_tracing()` in the driver; worker processes auto-enable
via the RAY_TPU_TRACING env var.

Program spans (`span`, `record_span`, `recent_spans`; second half of this
file) are another thing and always on: what a process was doing and when,
in `time.monotonic_ns()`, kept in a bounded buffer, written about once a
second to the session's `logs/spans-<worker>.jsonl` and, while a
`jax.profiler` session runs, laid on its host plane as `TraceAnnotation`s:
the device trace's own clock, under the device operations a span caused.
`ray_tpu timeline` draws the files (`util/timeline.py`).
"""

from __future__ import annotations

import contextvars
import itertools
import json
import os
import secrets
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager

_enabled = False
_otel_tracer = None

# (trace_id_hex32, span_id_hex16) of the active span in this task/process.
_current: contextvars.ContextVar[tuple[str, str] | None] = \
    contextvars.ContextVar("ray_tpu_trace", default=None)


def setup_tracing(tracer_provider=None) -> None:
    """Turn on tracing in this process. Optionally pass a configured
    opentelemetry SDK TracerProvider to also export real spans."""
    global _enabled, _otel_tracer
    _enabled = True
    os.environ["RAY_TPU_TRACING"] = "1"
    if tracer_provider is not None:
        from opentelemetry import trace

        trace.set_tracer_provider(tracer_provider)
        _otel_tracer = trace.get_tracer("ray_tpu")
    else:
        try:
            from opentelemetry import trace

            _otel_tracer = trace.get_tracer("ray_tpu")
        except ImportError:
            _otel_tracer = None


def maybe_setup_from_env() -> None:
    if not _enabled and os.environ.get("RAY_TPU_TRACING") == "1":
        setup_tracing()


def enabled() -> bool:
    return _enabled


def _format_traceparent(trace_id: str, span_id: str) -> str:
    return f"00-{trace_id}-{span_id}-01"


def _parse_traceparent(tp: str) -> tuple[str, str] | None:
    parts = tp.split("-")
    if len(parts) != 4 or len(parts[1]) != 32 or len(parts[2]) != 16:
        return None
    return parts[1], parts[2]


def current_traceparent() -> str:
    """W3C traceparent for the active context ('' when none). Prefers a
    live OTel span (SDK installed), else the built-in context."""
    if not _enabled:
        return ""
    try:
        from opentelemetry import trace

        ctx = trace.get_current_span().get_span_context()
        if ctx.trace_id:
            return _format_traceparent(format(ctx.trace_id, "032x"),
                                       format(ctx.span_id, "016x"))
    except ImportError:
        pass
    cur = _current.get()
    if cur is None:
        return ""
    return _format_traceparent(*cur)


@contextmanager
def _span(name: str, task_id: str, parent_tp: str | None):
    """Built-in span: continue the parent's trace (or the ambient one, or
    start fresh), plus an OTel span when an SDK is wired up."""
    parent = _parse_traceparent(parent_tp) if parent_tp else None
    if parent is None:
        ambient = _parse_traceparent(current_traceparent() or "")
        parent = ambient
    trace_id = parent[0] if parent else secrets.token_hex(16)
    span_id = secrets.token_hex(8)
    token = _current.set((trace_id, span_id))
    otel_cm = None
    try:
        if _otel_tracer is not None:
            from opentelemetry import trace as otrace

            ctx = None
            if parent:
                from opentelemetry.trace import (
                    NonRecordingSpan,
                    SpanContext,
                    TraceFlags,
                )
                from opentelemetry.trace.propagation import set_span_in_context

                ctx = set_span_in_context(NonRecordingSpan(SpanContext(
                    trace_id=int(parent[0], 16), span_id=int(parent[1], 16),
                    is_remote=True, trace_flags=TraceFlags(1))))
            otel_cm = _otel_tracer.start_as_current_span(
                name, context=ctx, attributes={"ray_tpu.task_id": task_id})
            otel_cm.__enter__()
        try:
            yield
        except BaseException:
            # Let the OTel span record the failure (status + exception
            # event) instead of exporting errored tasks as OK.
            if otel_cm is not None:
                import sys

                otel_cm.__exit__(*sys.exc_info())
                otel_cm = None
            raise
    finally:
        if otel_cm is not None:
            otel_cm.__exit__(None, None, None)
        _current.reset(token)


@contextmanager
def submit_span(name: str, task_id: str):
    """Span around client-side submission; yields the traceparent to embed
    in the TaskSpec (reference: _tracing_task_invocation)."""
    if not _enabled:
        yield ""
        return
    with _span(f"{name} ray_tpu.remote", task_id, None):
        yield current_traceparent()


@contextmanager
def execute_span(name: str, task_id: str, traceparent: str):
    """Span around worker-side execution, parented to the submitter's span
    (reference: _inject_tracing_into_function). A spec carrying trace
    context activates tracing here even if this worker predates the
    driver's setup_tracing() (workers inherit env only at spawn time)."""
    if traceparent and not _enabled:
        maybe_setup_from_env()
        if not _enabled:
            setup_tracing()
    if not _enabled:
        yield
        return
    with _span(f"{name} ray_tpu.execute", task_id, traceparent or None):
        yield


# ---------------------------------------------------------------------------
# Program spans
# ---------------------------------------------------------------------------

# Finished spans a process keeps (and at most as many not yet written).
SPAN_BUFFER = 16384
# A span file's cap. Two are kept, `spans-<label>.jsonl` and the one before
# it, `.jsonl.1`: a flight recorder, not a log that grows.
SPAN_FILE_BYTES = 8 << 20
SPAN_FLUSH_S = 1.0
SPAN_FILE_PREFIX = "spans-"

_SCALARS = (bool, int, float, str)


class _SpanRecorder:
    """One process's finished spans, newest last, and the daemon thread
    that writes them out. A record is a tuple (id, parent id or 0, name,
    rid, start ns, duration ns, thread ident, thread name, attrs)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._records: deque = deque(maxlen=SPAN_BUFFER)
        self.ids = itertools.count(1)
        self._recorded = 0      # records ever added
        self._written = 0       # of those, written out or given up
        self._writer: threading.Thread | None = None
        self._io_lock = threading.Lock()
        self._path: str | None = None
        self._bytes = 0

    def add(self, record: tuple) -> None:
        with self._lock:
            self._records.append(record)
            self._recorded += 1
        if self._writer is None:
            self._start_writer()

    def recent(self) -> list:
        with self._lock:
            return list(self._records)

    def _start_writer(self) -> None:
        with self._lock:
            if self._writer is not None:
                return
            self._writer = threading.Thread(
                target=self._write_loop, daemon=True, name="span-writer")
        self._writer.start()

    def _write_loop(self) -> None:
        while True:
            time.sleep(SPAN_FLUSH_S)
            self.flush()

    def flush(self) -> None:
        """Write what was recorded since the last flush to the session's
        logs; outside a session there is nowhere to write and the spans
        stay in memory (`recent_spans`)."""
        session = os.environ.get("RAY_TPU_SESSION_DIR")
        with self._io_lock:
            with self._lock:
                new = min(self._recorded - self._written, len(self._records))
                self._written = self._recorded
                if not session or not new:
                    return
                batch = list(itertools.islice(reversed(self._records), new))
            batch.reverse()
            try:
                self._write(os.path.join(session, "logs"), batch)
            except OSError:
                pass  # a full or vanished log directory must not stop a server

    def _write(self, logs: str, batch: list) -> None:
        label = os.environ.get("RAY_TPU_WORKER_ID", "")[:12] \
            or f"pid{os.getpid()}"
        path = os.path.join(logs, f"{SPAN_FILE_PREFIX}{label}.jsonl")
        data = "".join(json.dumps(span_dict(r), default=str) + "\n"
                       for r in batch)
        fresh = path != self._path or not os.path.exists(path)
        if not fresh and self._bytes + len(data) > SPAN_FILE_BYTES:
            os.replace(path, path + ".1")
            fresh = True
        if fresh:
            os.makedirs(logs, exist_ok=True)
            # Ties this file's monotonic stamps to the wall clock.
            data = json.dumps({"header": {
                "pid": os.getpid(), "label": label, "time_s": time.time(),
                "mono_ns": time.monotonic_ns()}}) + "\n" + data
            self._path, self._bytes = path, 0
        with open(path, "w" if fresh else "a") as f:
            f.write(data)
        self._bytes += len(data)


_recorder = _SpanRecorder()
_open_spans = threading.local()  # .stack: this thread's open spans


def _forget_parent_process() -> None:
    # A forked child (the worker zygote's) has none of the parent's
    # threads, and its locks may have been held at the fork.
    global _recorder, _open_spans
    _recorder = _SpanRecorder()
    _open_spans = threading.local()


os.register_at_fork(after_in_child=_forget_parent_process)


def span_dict(record: tuple) -> dict:
    """A record as it is written and as `recent_spans` returns it."""
    sid, parent, name, rid, t0, dur, tid, thread, attrs = record
    out = {"id": sid, "parent": parent or None, "name": name, "t0_ns": t0,
           "dur_ns": dur, "tid": tid, "thread": thread}
    if rid is not None:
        out["rid"] = rid
    if attrs:
        out["attrs"] = attrs
    return out


class Span:
    """One interval of this thread's work. `with span(...)`, or `begin()`
    and `end()` where a `with` would reshape the code around it; both ends
    on one thread. Attributes known only at the end go to `end()`."""

    __slots__ = ("name", "rid", "attrs", "id", "parent", "t0", "_annotation")

    def __init__(self, name: str, rid=None, **attrs):
        self.name = name
        self.rid = rid
        self.attrs = attrs
        self._annotation = None

    def begin(self) -> "Span":
        try:
            stack = _open_spans.stack
        except AttributeError:      # this thread's first span
            stack = _open_spans.stack = []
        self.id = next(_recorder.ids)
        self.parent = stack[-1].id if stack else 0
        stack.append(self)
        # Only where jax is already there: a span must not be what loads it.
        profiler = sys.modules.get("jax.profiler")
        annotate = getattr(profiler, "TraceAnnotation", None)
        if annotate is not None:
            meta = _scalars(self.attrs)
            if self.rid is not None:
                meta["rid"] = self.rid
            self._annotation = annotate(self.name, **meta)
            self._annotation.__enter__()
        self.t0 = time.monotonic_ns()
        return self

    def end(self, **attrs) -> None:
        t1 = time.monotonic_ns()
        if attrs:
            self.attrs.update(attrs)
        stack = _open_spans.stack
        if self in stack:
            # A span left open inside this one (an exception passed its
            # `end`) goes with it, so later spans get the right parent.
            while (inner := stack.pop()) is not self:
                inner._close_annotation()
        if attrs and self._annotation is not None:
            self._annotation.set_metadata(**_scalars(attrs))
        self._close_annotation()
        me = threading.current_thread()
        _recorder.add((self.id, self.parent, self.name, self.rid, self.t0,
                       t1 - self.t0, me.ident, me.name, self.attrs))

    def _close_annotation(self) -> None:
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None

    __enter__ = begin

    def __exit__(self, *exc) -> None:
        self.end()


def _scalars(attrs: dict) -> dict:
    # What a profiler annotation takes; a list (`rids`) stays in the record.
    return {k: v for k, v in attrs.items() if isinstance(v, _SCALARS)}


def span(name: str, rid=None, **attrs) -> Span:
    """A span of this thread's work, not yet begun: name, start and
    duration in `time.monotonic_ns()`, the thread, the enclosing span
    (`parent`) and `attrs`; `rid` is the request it belongs to, shared by
    every span of that request."""
    return Span(name, rid, **attrs)


def record_span(name: str, t0_ns: int, rid=None, t1_ns: int | None = None,
                **attrs) -> None:
    """A span that began at `t0_ns` (a `time.monotonic_ns()` reading,
    perhaps another thread's) and ends now, or at `t1_ns` where the end
    was seen before this call: a request's wait, which no one thread
    spends; a program's time on the chip, which no thread of the host
    spends at all. It has no parent and no profiler annotation; its `rid`
    ties it to the spans that served it."""
    me = threading.current_thread()
    if t1_ns is None:
        t1_ns = time.monotonic_ns()
    _recorder.add((next(_recorder.ids), 0, name, rid, t0_ns, t1_ns - t0_ns,
                   me.ident, me.name, attrs))


def recent_spans() -> list[dict]:
    """The spans this process finished, oldest first (at most SPAN_BUFFER)."""
    return [span_dict(r) for r in _recorder.recent()]


def flush_spans() -> None:
    """Write out now what the writer thread would within a second."""
    _recorder.flush()


def read_span_files(session_dir: str) -> list[tuple[dict, list[dict]]]:
    """[(header, spans)] of every span file under a session's logs, a
    file's older half (`.1`) before its newer. A file is read as far as
    it parses: its writer may be mid-line."""
    logs = os.path.join(session_dir, "logs")
    try:
        names = [n for n in os.listdir(logs)
                 if n.startswith(SPAN_FILE_PREFIX)]
    except OSError:
        return []
    out = []
    for name in sorted(names, key=lambda n: (n.removesuffix(".1"),
                                             not n.endswith(".1"))):
        header, spans = {}, []
        try:
            with open(os.path.join(logs, name)) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        break
                    if "header" in rec:
                        header = rec["header"]
                    else:
                        spans.append(rec)
        except OSError:
            continue
        out.append((header, spans))
    return out
