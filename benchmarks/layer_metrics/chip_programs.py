"""The chip's own row of the program's spans: `chip.program` (PR 55), one
span for every program the engine dispatched (a decode chunk; a prefill
group), written by the engine's watcher thread, its interval the chip's
(`PERF.md` section 3; README-chip-program.md beside this file).  The three
readers that take their numbers from it read the run's WHOLE window
(`obs["window"]`), the ramp and the first fill included, as the end-to-end
numbers do; found through `program_spans.session`, so a program that
records no such span (any parent of PR 55) gives None and the line leaves
the metric out.
"""

from benchmarks.harness.loader import sibling_reader

program_spans = sibling_reader(__file__, "program_spans")

SPAN = "chip.program"


def overlap_ns(a0: int, a1: int, b0: int, b1: int) -> int:
    return max(0, min(a1, b1) - max(a0, b0))


class Window:
    """The `chip.program` spans of the run whose window `obs` gives, and
    the window itself in nanoseconds of `time.monotonic_ns()`."""

    def __init__(self, spans, window):
        self.t0, self.t1 = (int(t * 1e9) for t in window)
        self.records = spans.records
        self.programs = [r for r in spans.records if r["name"] == SPAN]

    @property
    def ns(self) -> int:
        return self.t1 - self.t0

    def inside(self, t0_ns: int, t1_ns: int) -> int:
        """Nanoseconds of [t0_ns, t1_ns) that lie in the window."""
        return overlap_ns(t0_ns, t1_ns, self.t0, self.t1)

    def of_kind(self, kind: str) -> list:
        return [p for p in self.programs if p["attrs"]["kind"] == kind]


def window(obs) -> Window | None:
    """None where the run has no window, its session no span file, or the
    program wrote no `chip.program` into it."""
    spans = program_spans.session(obs.get("window"))
    if spans is None:
        return None
    found = Window(spans, obs["window"])
    return found if found.programs and found.ns > 0 else None
