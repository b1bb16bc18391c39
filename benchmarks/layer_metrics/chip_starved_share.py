"""engine: the share of the window in which the chip had NOTHING queued
though a request was there to serve.  100 x (the starved intervals
`[span start - starved_ns, span start]` of the `chip.program` spans,
clipped to the window, LESS what `engine.idle` spans of `why` `no_request`
cover of them) / window.  A program queued behind another has
`starved_ns` 0; what is left is the host's time between one program's end
and the next one's dispatch (`fetch_to_dispatch_ms`, as a share): the
fetch, the walk, an admission's host work, the build, a stall."""

from benchmarks.harness.loader import sibling_reader

LAYER = "engine"
UNIT = "%"
MOVES = "tpot_p90_ms"

chip_programs = sibling_reader(__file__, "chip_programs")


def read(obs):
    found = chip_programs.window(obs)
    if found is None:
        return None
    asleep = [(r["t0_ns"], r["t0_ns"] + r["dur_ns"]) for r in found.records
              if r["name"] == "engine.idle"
              and r.get("attrs", {}).get("why") == "no_request"]
    starved = 0
    for p in found.programs:
        t1 = min(p["t0_ns"], found.t1)
        t0 = max(p["t0_ns"] - p["attrs"]["starved_ns"], found.t0)
        if t0 >= t1:
            continue
        # (one thread's idle spans do not overlap each other)
        starved += t1 - t0 - sum(
            chip_programs.overlap_ns(t0, t1, a0, a1) for a0, a1 in asleep)
    return 100.0 * starved / found.ns
