"""`LLMEngine` over the SambaY family (`serve/llm_families.py`): pages of
one layer, rings and recurrent state behind the same loop, slots and
allocator that serve a Llama.  Tiny widths, float32, the benchmark's plain
reference as the judge: in float32 on the CPU the engine's greedy tokens
are the reference's argmax at every position (the top-2 margins of these
logits, 1e-3 and up, are far above float32 reordering, 5e-7).  Its
streams against the reference, as every family's:
`tests/test_families_served.py`.
"""

import time

import numpy as np
import pytest

from tests.tiny_families import ENGINE, prompts as _prompts
from tests.tiny_families import sambay as family


@pytest.fixture(scope="module")
def tiny():
    return family.cfg, family.params


@pytest.fixture(scope="module")
def engine(tiny):
    """One engine for the tests that read it and plant nothing in it: the
    first finds it new and leaves it held, the last asks it only what it
    refuses."""
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(*tiny, **ENGINE)
    yield eng
    eng.shutdown()


def test_a_parked_and_an_empty_slot_keep_their_state(engine):
    """The decode program with a count of steps a slot: a slot given none
    (parked, or empty) keeps rings, conv window and scan state bit for
    bit while its neighbours decode; its token, position and length stay."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import SamplingParams

    eng = engine            # new: the two streams get slots 0 and 1
    eng.quiesce_for_drain()
    prompts = _prompts(1, (21, 30))
    handles = [eng.submit(p, SamplingParams(max_new_tokens=40))
               for p in prompts]
    eng.resume()
    firsts = [next(iter(h)) for h in handles]   # both admitted
    assert len(firsts) == 2
    assert eng.quiesce_for_drain()
    B, K = eng.max_batch, eng.decode_chunk
    before = jax.tree_util.tree_map(np.array, eng._pools)   # copies
    steps = np.zeros(B, np.int32)
    steps[0] = K                    # slot 0 decodes, 1 is parked,
    toks, after, token, pos, lens, chunk_no = eng._decode_chunk_paged(
        eng.params, jnp.asarray(eng._token), jnp.asarray(eng._pos),
        eng._pools, jnp.asarray(eng._tables), jnp.asarray(eng._lens),
        jnp.asarray(eng._temps), jnp.asarray(eng._topks),
        jnp.asarray(eng._topps), jax.random.PRNGKey(0), jnp.int32(5),
        jnp.asarray(steps))          # 2 and 3 are empty
    eng._pools = after              # (the old buffers were donated)
    # The carry the program hands back: slot 0's cursor after K steps
    # and its last token, the others' as they were; the chunk's number.
    np.testing.assert_array_equal(lens, eng._lens + steps)
    np.testing.assert_array_equal(pos, eng._pos + steps)
    np.testing.assert_array_equal(token[1:], eng._token[1:])
    assert int(token[0]) == int(toks[K - 1, 0]) and int(chunk_no) == 6
    after = jax.tree_util.tree_map(np.asarray, after)
    fixed = lambda s: jax.tree_util.tree_leaves(  # noqa: E731
        {"rings": s["rings"], "mamba": s["mamba"]})
    # (layer 0's conv window holds its last three INPUTS, a function
    # of the tokens alone: a stream that repeats a token leaves it as
    # it was, so it is not asked to move)
    moved = [not np.array_equal(a[0], b[0])
             for a, b in zip(fixed(after), fixed(before))]
    assert all(moved[1:]), "the decoding slot's state did not advance"
    for a, b in zip(fixed(after), fixed(before)):
        np.testing.assert_array_equal(a[1:], b[1:])


def test_a_slow_consumer_parks_its_slot_and_loses_nothing(tiny):
    """A consumer that stops reading fills its queue (4 tokens); the slot
    is held still, not re-run: when the consumer comes back the stream is
    still the reference's, and the other stream never waited for it."""
    from ray_tpu.models.generate import SamplingParams
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = tiny
    eng = LLMEngine(cfg, params, stream_buffer=4, **ENGINE)
    try:
        slow_p, fast_p = _prompts(2, (18, 25))
        slow = eng.submit(slow_p, SamplingParams(max_new_tokens=30))
        fast = eng.submit(fast_p, SamplingParams(max_new_tokens=30))
        fast_out = fast.tokens()                 # slow is not read meanwhile
        assert eng.report_metrics()["parked_events"] > 0
        time.sleep(0.2)
        slow_out = slow.tokens()
        assert len(slow_out) == len(fast_out) == 30
        assert family.is_greedy(slow_p, slow_out)
        assert family.is_greedy(fast_p, fast_out)
    finally:
        eng.shutdown()


def test_what_the_family_cannot_do_is_refused_in_words(tiny, engine):
    from ray_tpu.serve.llm import LLMEngine, _Prefilled

    cfg, params = tiny
    with pytest.raises(TypeError, match="a new family is a class"):
        LLMEngine(object(), params, max_batch=2, max_len=64, page_size=16)
    with pytest.raises(NotImplementedError, match="prefilled where"):
        engine.submit_prefilled(_Prefilled([], 1, 4, 4, 0, [], True))
    with pytest.raises(NotImplementedError, match="prefilled where"):
        engine.snapshot_active_streams()


def test_the_family_sizes_the_batched_prefill_by_bucket(tiny):
    """Rows of one prefill dispatch: eight at most, fewer where a bucket's
    rows would pass 16,384 tokens, so the program of the largest bucket
    holds one row; the Llama family keeps eight at every bucket."""
    from ray_tpu.models.llama import TINY
    from ray_tpu.serve.llm_families import family_of

    cfg, _ = tiny
    fam = family_of(cfg, 17472)
    assert [fam.prefill_width(b, 32) for b in
            (512, 2048, 4096, 8192, 16384, 17472)] == [8, 8, 4, 2, 1, 1]
    assert fam.prefill_width(512, 4) == 4
    assert fam.prompt_pages(2048, 64) == 32
    llama = family_of(TINY, 2304)
    assert [llama.prefill_width(b, 32) for b in (64, 2048)] == [8, 8]
    # K/V of the bucket's length since the prompt attends over itself
    # (36, the pages of max_len, while the prefill held a dense cache)
    assert [llama.prompt_pages(b, 64) for b in (64, 2048, 2304)] == \
        [1, 32, 36]


def test_an_engines_prefill_computes_every_buckets_positions(tiny):
    """The family's prefill runs layers 0 .. L/2 over every position of a
    bucket and says nothing of what it computes, so `computed` on
    `engine.prefill` is every row's whole bucket; and the streams decoded
    from what those prefills wrote (the full layer at ONE query a row) are
    the reference's greedy tokens."""
    from ray_tpu.models.generate import SamplingParams
    from ray_tpu.serve.llm import LLMEngine
    from ray_tpu.util import tracing

    cfg, params = tiny
    eng = LLMEngine(cfg, params, **ENGINE)
    assert not hasattr(eng.family, "prefill_computed")
    try:
        seen = {s["id"] for s in tracing.recent_spans()}
        for prompt in _prompts(3, (40, 10)):
            out = eng.submit(prompt, SamplingParams(
                max_new_tokens=20)).tokens()
            assert len(out) == 20 and family.is_greedy(prompt, out)
    finally:
        eng.shutdown()
    got = [(s["attrs"]["bucket"], s["attrs"]["rows"], s["attrs"]["computed"])
           for s in tracing.recent_spans()
           if s["name"] == "engine.prefill" and s["id"] not in seen]
    assert got == [(64, 1, 64), (16, 1, 16)]
