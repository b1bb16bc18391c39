"""The made-up family with its program wired wrongly, for one test: the
engine is built with another rotary base than the file states (and the
reference is told), so the timed path computes other attention than the
model's.  A run over it must come out not `correct`."""

from __future__ import annotations

from benchmarks.harness import loader

_sound = loader.beside(__file__, "families", "other_decoder.py")
reference, REDUCIBLE, DEPTH_KEY = (_sound.reference, _sound.REDUCIBLE,
                                   _sound.DEPTH_KEY)
sizes, model, loss, check_file = (_sound.sizes, _sound.model, _sound.loss,
                                  _sound.check_file)


def program_config(sizes: dict, **overrides):
    return _sound.program_config(
        dict(sizes, rope_base=sizes["rope_base"] / 100), **overrides)
