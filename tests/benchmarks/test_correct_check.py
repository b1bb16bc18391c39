"""How `correct` is decided for a served model (`replica.check_reference`,
`serve_common.judge`), at TINY widths on the CPU: the unplanted streams
pass, and each planted fault makes its sample fail through the same code
a run on the chip goes through."""

import json
import os
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)
_HERE = os.path.dirname(os.path.abspath(__file__))

from benchmarks.harness import loader, serve_common  # noqa: E402

TOLERANCE = serve_common.LOGIT_TIE_TOLERANCE
PROMPTS = {"a": 20, "b": 28, "c": 16}     # c fills exactly one page
NEW_TOKENS = 40


@pytest.fixture(scope="module")
def replica():
    """The benchmark's replica, in this process: the framework's paged
    engine on seeded weights, as a lease-holder builds it."""
    from benchmarks.harness.replica import BenchLLM

    with open(os.path.join(_HERE, "cells", "configs", "tiny.json")) as f:
        conf = json.load(f)
    family = loader.load_family(loader.DEFAULT_FAMILY)
    llm = BenchLLM(family.sizes(conf), 2 ** 31 + 11, conf["serve"]["engine"])
    yield llm
    llm.engine.shutdown()


def _prompts(seed=5):
    rng = np.random.default_rng(seed)
    return {k: [int(t) for t in rng.integers(1, 256, n)]
            for k, n in PROMPTS.items()}


def _serve(llm, prompts: dict, before_chunk=None) -> dict:
    """All prompts admitted as one group, decoded together; `before_chunk`
    (n, args) -> args may tamper with the n-th decode dispatch."""
    from ray_tpu.models.generate import SamplingParams

    eng = llm.engine
    real, calls = eng._decode_chunk_paged, [0]

    def chunk(*args):
        calls[0] += 1
        return real(*(before_chunk(calls[0], list(args))
                      if before_chunk else args))

    eng._decode_chunk_paged = chunk
    try:
        eng.quiesce_for_drain()
        handles = {k: eng.submit(p, SamplingParams(max_new_tokens=NEW_TOKENS))
                   for k, p in prompts.items()}
        eng.resume()
        return {k: h.tokens() for k, h in handles.items()}
    finally:
        eng._decode_chunk_paged = real


def _judged(llm, prompts, streams, **kw) -> dict:
    samples = [{"rid": k, "prompt": prompts[k], "output": streams[k]}
               for k in sorted(streams)]
    compared = llm.check_reference(samples, TOLERANCE, **kw)
    return {c["rid"]: (serve_common.judge(c), c) for c in compared}


@pytest.fixture(scope="module")
def sound(replica):
    prompts = _prompts()
    return prompts, _serve(replica, prompts)


def test_the_unplanted_streams_pass_and_nothing_is_set_aside(replica, sound):
    prompts, streams = sound
    assert all(len(s) == NEW_TOKENS for s in streams.values())
    for problem, c in _judged(replica, prompts, streams, diagnose=8).values():
        assert problem is None
        # float32 on the CPU: the engine's tokens are the reference's own,
        # and the rounded passes move none of them past the tolerance.
        assert c["max_logit_gap"] == 0.0 and c["over"] == []
        assert c["set_aside"] == 0 and c["kept_max_gap"] == 0.0


def test_a_token_swapped_for_the_fifth_best_fails(replica, sound):
    prompts, streams = sound
    lg = replica.reference_logits(prompts["a"], streams["a"])
    fifth = np.argsort(lg, -1)[:, -5]
    k = 17
    assert lg[k].max() - lg[k, fifth[k]] > 2 * TOLERANCE
    planted = dict(streams, a=streams["a"][:k] + [int(fifth[k])]
                   + streams["a"][k + 1:])
    judged = _judged(replica, prompts, planted)
    problem, c = judged["a"]
    assert problem and "under the reference's best" in problem
    # The gap is reported with its position, and is not set aside: only
    # the reference could have done that, and it is sure of its token.
    assert k in [o[0] for o in c["over"]] and k not in c["set_aside_at"]
    assert judged["b"][0] is None and judged["c"][0] is None


def test_a_chunk_decoded_from_another_slots_pages_fails(replica, sound):
    prompts, streams = sound

    def swap_tables(n, args):
        if n == 3:                       # one chunk, mid-stream
            tables = np.asarray(args[4]).copy()
            tables[[0, 1]] = tables[[1, 0]]
            args[4] = tables
        return args

    planted = _serve(replica, prompts, swap_tables)
    assert planted != streams
    judged = _judged(replica, prompts, planted, diagnose=8)
    failed = [k for k, (problem, _c) in judged.items() if problem]
    assert failed and set(failed) <= {"a", "b"}      # slots 0 and 1
    for k in failed:
        c = judged[k][1]
        # The first miss lies in the tampered chunk (tokens 1 + 4 x 2 ...),
        # and what `diagnose` adds says where to look: alone on the empty
        # engine the stream is the sound one.
        first = c["over"][0][0]
        assert 9 <= first < 13, c["over"]
        assert c["diagnosis"]["alone"]["over"] == []
        assert not c["diagnosis"]["alone"]["same_tokens"]
    assert judged["c"][0] is None


@pytest.mark.parametrize("bits, fails", [(4, True), (8, False)])
def test_keys_and_values_held_in_fewer_bits_fail(replica, sound, bits, fails):
    """The control: the reference in the program's place with K and V in a
    lower precision than the configuration states, decoding greedily.  At
    these widths (float32, 256 tokens, margins of a few tenths) four bits
    fail every sample and eight move no token past the tolerance; at a
    cell's own widths eight bits are the control, and that reading comes
    from the chip (`harness/diagnose.py` `control_8bit_gap`; PERF.md)."""
    prompts, _streams = sound
    reference = replica._family.reference

    def decode(prompt):
        seq = list(prompt)
        for _ in range(NEW_TOKENS):
            lg = reference.logits(replica._params, replica._sizes,
                                  seq + [0] * (128 - len(seq)),
                                  [len(seq) - 1], kv_bits=bits)
            seq.append(int(np.asarray(lg)[0].argmax()))
        return seq[len(prompt):]

    control = {k: decode(p) for k, p in prompts.items()}
    judged = _judged(replica, prompts, control)
    assert [bool(problem) for problem, _c in judged.values()] == [fails] * 3
    if fails:
        assert min(c["kept_max_gap"] for _p, c in judged.values()) > \
            3 * TOLERANCE


# ---- the rule that sets positions aside, on a reference made by hand ---------


class _HandMadeReference:
    """Logits over 8 tokens at 40 positions: the reference's best is token
    1 by a margin of 1 everywhere; its third rounded pass (the whole
    served type) puts token 2 above it by 3 at the positions `unstable`."""

    ROUNDINGS = ("none", "kv", "residual", "all")

    def __init__(self, unstable):
        self.unstable = list(unstable)

    def logits(self, params, sizes, tokens, rows, rounded=0, kv_bits=0):
        lg = np.zeros((len(rows), 8), np.float32)
        lg[:, 1] = 1.0
        if rounded == 3:
            lg[self.unstable, 2] = 4.0
        return lg


def _judge_by_hand(unstable, engine_misses):
    import types

    from benchmarks.harness.replica import BenchLLM

    stream = [1] * 40
    for k in engine_misses:
        stream[k] = 2
    fake = object.__new__(BenchLLM)      # the check alone, no engine
    fake._family = types.SimpleNamespace(
        reference=_HandMadeReference(unstable))
    fake._params = fake._sizes = None
    fake.engine = types.SimpleNamespace(max_len=64)
    (c,) = fake.check_reference(
        [{"rid": 7, "prompt": [3] * 8, "output": stream}], TOLERANCE)
    return serve_common.judge(c), c


@pytest.mark.parametrize("unstable, engine_misses, problem", [
    ([], [], None),
    # The engine misses where the served type cannot decide: set aside.
    ([5, 6], [5, 6], None),
    ([5, 6], [6], None),
    # It misses elsewhere: what the reference set aside does not help it.
    ([5, 6], [5, 9], "under the reference's best"),
    ([], [9], "under the reference's best"),
    # A sample the served type cannot decide at more than one position in
    # ten is refused, though the engine misses only there.
    ([3, 5, 6, 11, 20], [5], "set aside 5 of 40"),
], ids=["sound", "misses-set-aside", "one-of-them", "a-miss-elsewhere",
        "a-miss-nothing-aside", "too-many-set-aside"])
def test_positions_are_set_aside_by_the_reference_alone(unstable,
                                                        engine_misses,
                                                        problem):
    got, c = _judge_by_hand(unstable, engine_misses)
    assert (got is None) if problem is None else (problem in got), got
    if engine_misses:
        assert [o[0] for o in c["over"]] == engine_misses
        assert c["set_aside_at"] == unstable
        assert c["set_aside_by_pass"] == [0, 0, len(unstable)]
    else:
        # No gap: the rounded passes are not made at all.
        assert c["set_aside"] is None and c["over"] == []
