"""The program's own spans (`ray_tpu/util/tracing.py`, PR 40), read from
where the program writes them: `<temp dir>/session-*/logs/spans-*.jsonl`
(and each file's older half, `.jsonl.1`), JSON lines stamped in
`time.monotonic_ns()`: the clock of `obs["window"]` and of
`obs["trace"]["window_mono_s"]` (one host, one CLOCK_MONOTONIC).  `obs`
has no key for them, so the readers come here; README-program-spans.md
beside this file says what to delete once it has.

Nothing of `ray_tpu.util.tracing` is imported: on a program that writes no
spans (the parent of PR 40) there is no file, `session` returns None and
each reader leaves its metric out.
"""

import glob
import json
import os

# A span file's last write may lag its newest span by the writer's period.
STALE_S = 5.0


def temp_dir() -> str:
    """Where the runtime keeps its sessions (`RAY_TPU_TEMP_DIR` over the
    default), as the runtime itself reads it."""
    from ray_tpu._private.config import Config

    return Config().temp_dir


class SessionSpans:
    """Every span of one session's files, and those that start in the
    interval asked for."""

    def __init__(self, records: list, t0_ns: int, t1_ns: int):
        self.records = records
        self._t0, self._t1 = t0_ns, t1_ns
        self._children: dict = {}
        for r in records:
            if r.get("parent"):
                self._children.setdefault(
                    (r["proc"], r["parent"]), []).append(r)

    def starts_inside(self, r: dict) -> bool:
        return self._t0 <= r["t0_ns"] < self._t1

    def named(self, name: str) -> list:
        return [r for r in self.records
                if r["name"] == name and self.starts_inside(r)]

    def descendants(self, r: dict):
        for child in self._children.get((r["proc"], r["id"]), []):
            yield child
            yield from self.descendants(child)

    def host_only_ns(self, r: dict) -> int:
        """The span less what its `*.wait` descendants cover: the host's
        own time in it, the device's left out (`choosing-metrics`, section
        4: self time is the span less what its children cover; the waits
        do not overlap, one thread runs them)."""
        return r["dur_ns"] - sum(d["dur_ns"] for d in self.descendants(r)
                                 if d["name"].endswith(".wait"))


def _records(path: str, t0_ns: int) -> list:
    """The file's spans, or none where the file was last written before
    the interval began (told from its header, which pairs a wall-clock
    reading with a monotonic one); read as far as it parses."""
    out = []
    proc = os.path.basename(path).split(".jsonl")[0]
    try:
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    break
                head = rec.get("header")
                if head is not None:
                    began = head["time_s"] + (t0_ns - head["mono_ns"]) / 1e9
                    if os.path.getmtime(path) < began - STALE_S:
                        return []
                    proc = f"{proc}:{head.get('pid')}"
                    continue
                rec["proc"] = proc
                out.append(rec)
    except OSError:
        return []
    return out


def session(interval) -> SessionSpans | None:
    """The spans of the newest session that has one starting in
    `interval` (seconds of `time.monotonic()`); None where no session
    has: the program writes none, or the interval is not this host's."""
    if not interval:
        return None
    t0_ns, t1_ns = (int(t * 1e9) for t in interval)
    sessions = sorted(glob.glob(os.path.join(temp_dir(), "session-*")),
                      key=os.path.getmtime, reverse=True)
    for path in sessions:
        records = []
        for name in sorted(glob.glob(
                os.path.join(path, "logs", "spans-*.jsonl*"))):
            records.extend(_records(name, t0_ns))
        spans = SessionSpans(records, t0_ns, t1_ns)
        if any(spans.starts_inside(r) for r in records):
            return spans
    return None


def traced_slot(obs) -> tuple | None:
    trace = obs.get("trace")
    return tuple(trace["window_mono_s"]) \
        if trace and trace.get("window_mono_s") else None
