"""model step: what a thousand prompt tokens cost the chip.  (Chip
milliseconds of the `chip.program` spans of kind `prefill` that END in the
window) / (the sum of their `prompt_tokens` / 1000): whichever programs
fall where, and whatever the buckets' padding, a run's prompts are the
traffic file's, so two commits are compared on the same work."""

from benchmarks.harness.loader import sibling_reader

LAYER = "model step"
UNIT = "ms/ktok"
MOVES = "tpot_p90_ms"

chip_programs = sibling_reader(__file__, "chip_programs")


def read(obs):
    found = chip_programs.window(obs)
    if found is None:
        return None
    ended = [p for p in found.of_kind("prefill")
             if found.t0 <= p["t0_ns"] + p["dur_ns"] < found.t1]
    tokens = sum(p["attrs"]["prompt_tokens"] for p in ended)
    if not tokens:
        return None
    return sum(p["dur_ns"] for p in ended) / 1e6 / (tokens / 1000.0)
