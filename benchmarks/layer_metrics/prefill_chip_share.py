"""model step: the share of the window the chip spent on prefill programs.
100 x (time of the `chip.program` spans of kind `prefill`, clipped to the
window) / window: a prefill group's three dispatches (prefill, the write
into pages and slots, sampling) are one span, whose interval is the chip's
(it starts when the group was queued or the program before it ended, and
ends where the watcher or the loop's fetch saw it end).  The whole window,
not the traced slot: the ramp and the first fill count, as they do in the
end-to-end number.  What chunked prefill and faster prefill programs move."""

from benchmarks.harness.loader import sibling_reader

LAYER = "model step"
UNIT = "%"
MOVES = "tpot_p90_ms"

chip_programs = sibling_reader(__file__, "chip_programs")


def read(obs):
    found = chip_programs.window(obs)
    if found is None:
        return None
    on = sum(found.inside(p["t0_ns"], p["t0_ns"] + p["dur_ns"])
             for p in found.of_kind("prefill"))
    return 100.0 * on / found.ns
