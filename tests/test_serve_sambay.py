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


def _prefills(kind, seen):
    from ray_tpu.util import tracing

    return [s["attrs"] for s in tracing.recent_spans()
            if s["name"] == kind and s["id"] not in seen
            and s["attrs"].get("kind", "prefill") == "prefill"]


def test_an_engines_prefill_computes_rows_x_blocks_x_block(tiny):
    """The family prefills from the host, a block of positions (a window
    here: 8) a dispatch: a group's `computed` is its rows x the blocks of
    its longest prompt x the block, whatever its bucket, on `engine.prefill`
    and on the chip's own `chip.program`, which both carry `blocks`; and
    the streams decoded from what the blocks and the tail wrote, a row
    alone and a group whose rows end in different blocks, are the
    reference's greedy tokens."""
    from ray_tpu.serve.llm import LLMEngine
    from ray_tpu.util import tracing

    from tests.tiny_families import serve

    cfg, params = tiny
    eng = LLMEngine(cfg, params, **ENGINE)
    assert eng.family.block == cfg.window == 8
    assert not hasattr(eng.family, "prefill")
    try:
        seen = {s["id"] for s in tracing.recent_spans()}
        alone = _prompts(3, (40, 10))
        for prompt in alone:
            out, = serve(eng, [prompt], 20)
            assert len(out) == 20 and family.is_greedy(prompt, out)
        group = _prompts(4, (50, 33, 41))
        for prompt, out in zip(group, serve(eng, group, 12)):
            assert len(out) == 12 and family.is_greedy(prompt, out)
        time.sleep(0.05)        # (the watcher writes a program's span)
    finally:
        eng.shutdown()
    want = [(64, 1, 1, 5, 40), (16, 1, 1, 2, 16), (64, 3, 4, 7, 168)]
    for kind in ("engine.prefill", "chip.program"):
        assert [(a["bucket"], a["rows"], a["width"], a["blocks"],
                 a["computed"]) for a in _prefills(kind, seen)] == want
    assert [a["prompt_tokens"] for a in _prefills("chip.program", seen)] \
        == [40, 10, 124]


def test_a_family_that_prefills_in_one_program_dispatches_what_it_did():
    """The seam leaves a family without `prefill_from_host` where it was:
    a Llama engine's groups go through the same two jitted callables
    (`prefill_many` for a group, `prefill_one` for a request alone), called
    once a group with device arrays, and their spans carry no `blocks`."""
    import jax

    from ray_tpu.serve.llm import LLMEngine
    from ray_tpu.util import tracing

    from tests.tiny_families import dense, serve

    eng = LLMEngine(dense.cfg, dense.params, **ENGINE)
    assert not hasattr(eng.family, "prefill_from_host")
    assert not hasattr(eng.family, "prompt_blocks")
    calls = []
    real = {"many": eng._prefill_many, "one": eng._prefill_one}

    def spy(which):
        def call(params, tokens, last_idx):
            assert isinstance(tokens, jax.Array) \
                and isinstance(last_idx, jax.Array)
            calls.append((which, tokens.shape))
            return real[which](params, tokens, last_idx)
        return call

    eng._prefill_many, eng._prefill_one = spy("many"), spy("one")
    try:
        seen = {s["id"] for s in tracing.recent_spans()}
        group = _prompts(5, (20, 30, 17))
        alone, = _prompts(6, (9,))
        outs = serve(eng, group, 6) + serve(eng, [alone], 6)
        assert all(dense.is_greedy(p, o)
                   for p, o in zip(group + [alone], outs))
    finally:
        eng.shutdown()
    assert calls == [("many", (4, 32)), ("one", (1, 16))]
    assert all("blocks" not in a and a["computed"] == a["bucket"] * a["rows"]
               for a in _prefills("engine.prefill", seen))
