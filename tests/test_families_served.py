"""`LLMEngine` over every family but the dense one (`serve/llm_families.py`):
pages, rings, fixed per-slot state and routed experts behind the same loop,
slots and allocator that serve a Llama.  ONE test, a case a family
(`tests/tiny_families.py` has the models): tiny widths, float32, the
benchmark's plain reference as the judge.  In float32 on the CPU the
engine's greedy tokens are the reference's argmax at every position (the
top-2 margins of these logits, 1e-3 and up, are far above float32
reordering, 5e-6 at most).  What a family's programs counted on the way is
checked after it, family by family.
"""

import numpy as np
import pytest

from ray_tpu.util import tracing
from tests.tiny_families import ENGINE, FAMILIES, prompts, serve

# Requests over four slots (batched prefills of rows of very different
# lengths in one padded bucket, singles, admission mid-flight, every slot
# used at least twice; SambaY's six use two slots twice), and the tokens
# each decodes.
LENGTHS = (5, 19, 33, 40, 17, 64, 28, 3, 50)
TRAFFIC = {"sambay": (LENGTHS[:6], 24), "granite_hybrid": (LENGTHS, 24),
           "lfm2_moe": (LENGTHS, 16), "mla_moe": (LENGTHS, 16),
           "granite_moe_hybrid": (LENGTHS, 16)}


class Spans:
    """The spans this process finishes from now on (the ring holds every
    engine's of the process: an earlier case's are not this engine's)."""

    def __init__(self):
        self._before = {s["id"] for s in tracing.recent_spans()}

    def __call__(self, name, attr):
        return [s["attrs"] for s in tracing.recent_spans()
                if s["id"] not in self._before and s["name"] == name
                and attr in s.get("attrs", {})]


def _sambay(got, spans, lengths, new, repeats):
    assert got["state_slots_reset"] == len(lengths)
    assert got["paged_pages_live"] > 0


def _granite_hybrid(got, spans, lengths, new, repeats):
    # the streams are not echoes of their input: the layers decide
    assert np.mean(repeats) < 0.2
    assert got["state_slots_reset"] == len(lengths)
    assert got["paged_pages_live"] > 0
    # what the fixed state costs, for a reader that knows no model:
    # six Mamba-2 layers of (3, 160) conv inputs and (4, 32, 16) state
    assert got["state_bytes_per_slot"] == 6 * (3 * 160 + 4 * 32 * 16) * 4
    # every token but a stream's first came from a decode step of a
    # live slot (a chunk may run past a stream's end by less than 4)
    decoded = len(lengths) * (new - 1)
    assert decoded <= got["state_slot_steps"] < decoded + 4 * len(lengths)


def _lfm2_moe(got, spans, lengths, new, repeats):
    assert np.mean(repeats) < 0.2       # not echoes of the input
    assert got["state_slots_reset"] == len(lengths)
    # seven conv layers... of (2, 64) float32 windows: five here
    assert got["state_bytes_per_slot"] == 5 * 2 * 64 * 4
    # every real prompt token and every padding row's one, twice in
    # each of six routed layers
    assert got["expert_rows"] >= sum(lengths) * 2 * 6
    assert 0 < got["experts_touched"] <= got["expert_slots"]
    assert got["expert_slots"] == got["decode_passes"] * 4 * 6 * 8
    waits = spans("engine.decode.wait", "expert_slots")
    assert waits and all(a["expert_slots"] == 4 * 6 * 8 and
                         a["experts_touched"] <= a["expert_slots"] and
                         1 <= a["expert_rows_max"] <= 4 for a in waits)
    fills = spans("engine.prefill.wait", "expert_rows")
    assert sum(a["expert_rows"] for a in fills) == got["expert_rows"]


def _mla_moe(got, spans, lengths, new, repeats):
    assert got["state_bytes_per_slot"] == 0
    # every real prompt token, three pairs in each of two routed layers
    assert got["expert_rows"] >= sum(lengths) * 3 * 2
    assert 0 < got["experts_touched"] <= got["expert_slots"]
    assert got["expert_slots"] == got["decode_passes"] * 4 * 2 * 8
    # (a token's bytes are the configuration's constant: the reader's
    # costs module has them, no counter carries them)
    assert "latent_bytes_per_token" not in got
    waits = spans("engine.decode.wait", "latent_tokens")
    assert waits and all(
        a["expert_slots"] == 4 * 2 * 8 and
        a["experts_touched"] <= a["expert_slots"] and
        0 < a["latent_tokens"] <= 4 * 4 * 128 for a in waits)
    assert sum(a["latent_tokens"] for a in waits) == got["latent_tokens"]
    fills = spans("engine.prefill.wait", "expert_rows")
    assert sum(a["expert_rows"] for a in fills) == got["expert_rows"]


def _granite_moe_hybrid(got, spans, lengths, new, repeats):
    assert np.mean(repeats) < 0.2       # not echoes of the input
    assert got["state_slots_reset"] == len(lengths)
    # six Mamba-2 layers of (3, 160) conv inputs and (4, 32, 16) state
    assert got["state_bytes_per_slot"] == 6 * (3 * 160 + 4 * 32 * 16) * 4
    # every real prompt token, three pairs in each of eight layers, every
    # expert held: a pair the router makes is a pair held
    assert got["expert_rows"] >= sum(lengths) * 3 * 8
    assert 0 < got["experts_touched"] <= got["expert_slots"]
    assert got["expert_slots"] == got["decode_passes"] * 4 * 8 * 8
    assert 0 < got["expert_pairs"] == got["expert_pairs_held"]
    waits = spans("engine.decode.wait", "expert_pairs")
    assert waits and all(
        a["expert_slots"] == 4 * 8 * 8 and
        a["experts_touched"] <= a["expert_slots"] and
        a["expert_pairs_held"] == a["expert_pairs"] <= 4 * 4 * 3 * 8 and
        1 <= a["expert_rows_max"] <= 4 for a in waits)
    assert sum(a["expert_pairs"] for a in waits) == got["expert_pairs"]
    fills = spans("engine.prefill.wait", "expert_rows")
    assert sum(a["expert_rows"] for a in fills) == got["expert_rows"]


COUNTED = {"sambay": _sambay, "granite_hybrid": _granite_hybrid,
           "lfm2_moe": _lfm2_moe, "mla_moe": _mla_moe,
           "granite_moe_hybrid": _granite_moe_hybrid}


@pytest.mark.parametrize("name", list(TRAFFIC))
def test_engine_streams_are_the_references_greedy(name):
    """A reused slot starts from what its own prefill computed from zero,
    or the second stream in it would leave the reference; what the
    family's programs counted is on the spans and in `report_metrics()`."""
    from ray_tpu.serve.llm import LLMEngine

    fam = FAMILIES[name]
    lengths, new = TRAFFIC[name]
    spans = Spans()
    eng = LLMEngine(fam.cfg, fam.params, **ENGINE)
    try:
        asked = prompts(0, lengths)
        outs = serve(eng, asked, new)
        repeats = []
        for p, o in zip(asked, outs):
            assert len(o) == new
            gap, repeat = fam.reference_gap(p, o)
            assert gap.max() == 0.0
            repeats.append(repeat)
        assert eng.num_active() == 0
        COUNTED[name](eng.report_metrics(), spans, lengths, new, repeats)
    finally:
        eng.shutdown()
