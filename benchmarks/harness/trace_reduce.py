"""From a profiler trace to numbers: device busy and idle, the time of each
program and of the kernels inside it, the costliest device operations and
the longest idle gaps.  Kept with the benchmark so that every PR computes
the same number the same way.

Two stages.  `load_events` reads an `.xplane.pb` with nothing but jax
(`jax.profiler.ProfileData`) into plain lists; `reduce_events` is pure
arithmetic on those lists and is what the recorded trace in the tests
checks.  A v5e trace has one plane per chip, `/device:TPU:<n>`, whose
line `XLA Modules` holds one event per program run (`jit_<function>(<id>)`)
and whose line `XLA Ops` holds one event per operation inside it, named by
its whole HLO text (`%attn.160 = bf16[...] custom-call(...),
custom_call_target="tpu_custom_call"`).  `load_events` keeps the
instruction's own name (`attn.160`) and marks a Pallas kernel by what it
is, not by what it is called: `tpu_custom_call:attn.160`.  The clock is nanoseconds since the trace began;
`SYNC_MARK`, a host annotation the benchmark writes while it reads
`time.monotonic_ns()`, ties it to the benchmark's own spans.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

SYNC_MARK = "bench_clock_sync"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_PROGRAM = re.compile(r"^(?:jit_|pjit_)?(.*?)(?:\(\d+\))?$")
_OP_FAMILY = re.compile(r"[.\-_]?\d+$")
_HLO_NAME = re.compile(r"^%?([^\s=]+)")
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
KERNEL_MARK = "tpu_custom_call:"
# Operations that only contain others (a scan is one `while`): their time
# is their children's, so they are left out of the list of costliest ops.
WRAPPERS = ("while", "conditional", "call")


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


KEEP_ENV = "BENCH_KEEP_TRACE"


def keep_copy(path: str) -> None:
    """Where BENCH_KEEP_TRACE names a directory, leave a copy of the raw
    trace there (to look at one by hand, and to record the small trace the
    tests keep: tools/trim_trace.py)."""
    keep = os.environ.get(KEEP_ENV)
    if keep:
        import shutil

        os.makedirs(keep, exist_ok=True)
        shutil.copy(path, keep)


class Tracer:
    """`jax.profiler` around a slot of the window, in the process that
    holds the chip: start, stop, then reduce the trace where it was
    written.  `marks` are `time.monotonic_ns()` readings; the one taken
    inside SYNC_MARK ties the trace's clock to them."""

    def __init__(self):
        self._dir: str | None = None
        self.marks: dict = {}

    @property
    def started(self) -> bool:
        return "t0_mono_ns" in self.marks

    @property
    def running(self) -> bool:
        return self.started and "t1_mono_ns" not in self.marks

    def start(self) -> None:
        import tempfile
        import time

        import jax

        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(SYNC_MARK):
            self.marks["sync_mono_ns"] = time.monotonic_ns()
        self.marks["t0_mono_ns"] = time.monotonic_ns()

    def stop(self) -> None:
        import time

        import jax

        self.marks["t1_mono_ns"] = time.monotonic_ns()
        jax.profiler.stop_trace()

    def reduce(self, make_label) -> dict | None:
        """`reduce_events` over the traced slot; `make_label(off_ns)`
        returns the gap labeller for a trace clock that lags
        `time.monotonic_ns()` by `off_ns`.  None if nothing was traced."""
        import shutil

        if self._dir is None:
            return None
        try:
            path = find_xplane(self._dir)
            if path is None:
                return None
            keep_copy(path)
            events = load_events(path)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None
        window, label = None, None
        if events["sync_ns"] is not None:
            off = self.marks["sync_mono_ns"] - events["sync_ns"]
            window = (self.marks["t0_mono_ns"] - off,
                      self.marks["t1_mono_ns"] - off)
            label = make_label(off)
        reduced = reduce_events(events, window, label)
        if reduced is not None:
            reduced["window_mono_s"] = (self.marks["t0_mono_ns"] / 1e9,
                                        self.marks["t1_mono_ns"] / 1e9)
            reduced["clock_synced"] = events["sync_ns"] is not None
        return reduced


def load_events(path: str) -> dict:
    """{"devices": {plane: {"modules": [...], "ops": [...]}}, "sync_ns":
    trace time of SYNC_MARK or None}; events are [name, start_ns, dur_ns]."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, sync_ns = {}, None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            devices[plane.name] = {
                key: [[fix(e.name), float(e.start_ns), float(e.duration_ns)]
                      for e in lines[name].events] if name in lines else []
                for key, name, fix in (("modules", MODULES_LINE, str),
                                       ("ops", OPS_LINE, op_name))}
        elif plane.name.startswith("/host:") and sync_ns is None:
            for ln in plane.lines:
                for e in ln.events:
                    if e.name == SYNC_MARK:
                        sync_ns = float(e.start_ns) + float(e.duration_ns) / 2
                        break
                if sync_ns is not None:
                    break
    return {"devices": devices, "sync_ns": sync_ns}


def program_name(module_event_name: str) -> str:
    return _PROGRAM.match(module_event_name).group(1)


def op_name(hlo_text: str) -> str:
    """`%fusion.226 = bf16[...] fusion(...)` -> `fusion.226`; a Pallas
    kernel keeps its mark: `tpu_custom_call:attn.160`."""
    name = _HLO_NAME.match(hlo_text).group(1)
    return KERNEL_MARK + name if KERNEL_TARGET in hlo_text else name


def is_kernel(name: str) -> bool:
    return name.startswith(KERNEL_MARK)


def union_intervals(events: list) -> list:
    """Sorted, merged [start, end] of events [name, start, dur]."""
    out = []
    for _n, s, d in sorted(events, key=lambda e: e[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return out


def _clip(intervals: list, t0: float, t1: float) -> list:
    return [[max(s, t0), min(e, t1)] for s, e in intervals
            if e > t0 and s < t1]


SHORT_GAP_NS = 50_000.0
SHORT_GAPS = "gaps under 0.05 ms between operations"


def reduce_events(events: dict, window_ns: tuple | None = None,
                  label_gap=None) -> dict | None:
    """Reduce `load_events`' output over `window_ns` = (t0, t1) on the
    trace's clock (default: first to last device event).  `label_gap(t0,
    t1)` names an idle gap by what the host was doing.  None when no
    operation ran on a device."""
    devices = {k: v for k, v in events["devices"].items() if v["ops"]}
    if not devices:
        return None
    if window_ns is None:
        starts = [e[1] for d in devices.values() for e in d["ops"]]
        ends = [e[1] + e[2] for d in devices.values() for e in d["ops"]]
        window_ns = (min(starts), max(ends))
    t0, t1 = window_ns
    busy, programs, kernels, op_time, gaps = [], {}, {}, {}, {}
    for dev in devices.values():
        merged = _clip(union_intervals(dev["ops"]), t0, t1)
        busy.append(sum(e - s for s, e in merged))
        edges = [t0] + [t for iv in merged for t in iv] + [t1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                if ge - gs < SHORT_GAP_NS:
                    name = SHORT_GAPS
                else:
                    name = label_gap(gs, ge) if label_gap else "unattributed"
                gaps[name] = gaps.get(name, 0.0) + (ge - gs)
        mods = sorted((m for m in dev["modules"] if t0 <= m[1] < t1),
                      key=lambda m: m[1])
        mod_starts = [m[1] for m in mods]
        for name, _s, d in mods:
            programs.setdefault(program_name(name), []).append(d)
        for name, s, d in dev["ops"]:
            if not t0 <= s < t1:
                continue
            family = _OP_FAMILY.sub("", name)
            if family not in WRAPPERS:
                op_time[family] = op_time.get(family, 0.0) + d
            if is_kernel(name):
                i = bisect.bisect_right(mod_starts, s) - 1
                if i >= 0 and s < mods[i][1] + mods[i][2]:
                    kernels.setdefault(program_name(mods[i][0]),
                                       []).append(d)
    top = lambda d: [[k, v / 1e9] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "n_devices": len(devices),
        "program_ns": programs,      # program -> durations of its runs
        "kernel_ns": kernels,        # program -> durations of its kernels
        "device_ops": top(op_time),
        "idle_gaps": top(gaps),
    }
