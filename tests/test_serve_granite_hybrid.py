"""`LLMEngine` over the Granite hybrid family (`serve/llm_families.py`): a
(k, v) pool for each attention layer and Mamba-2 state fixed per slot,
behind the same loop, slots and allocator that serve a Llama.  Tiny
widths, float32, the benchmark's plain reference as the judge: in float32
on the CPU the engine's greedy tokens are the reference's argmax at every
position (the top-2 margins of these logits, 1e-3 and up, are far above
float32 reordering, 5e-6).  Its streams against the reference, as every
family's: `tests/test_families_served.py`.
"""

import numpy as np
import pytest

from tests.tiny_families import ENGINE, prompts as _prompts, serve
from tests.tiny_families import granite_hybrid as family

LENGTHS = (5, 19, 33, 40, 17, 64, 28, 3, 50)


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


def test_a_reused_slot_that_keeps_its_last_streams_state_is_seen(tiny,
                                                                 monkeypatch):
    """The planted fault: an admission that does not clear S, so that
    the new stream's state is added to what the slot's last stream left
    there.  The first stream of each slot finds zeros and is sound; the
    streams that reuse a slot are not the reference's."""
    import jax.numpy as jnp

    from ray_tpu.serve import llm_families
    from ray_tpu.serve.llm import LLMEngine

    sound = llm_families.GraniteHybridServing.write_prompt

    def write_prompt(self, state, fresh, slots, page_ids):
        new = sound(self, state, fresh, slots, page_ids)
        B = state["ssm"][0][1].shape[0]
        written = jnp.zeros((B, 1, 1, 1)).at[slots].set(1.0, mode="drop")
        return dict(new, ssm=[(conv, s + written * left) for (conv, s),
                              (_, left) in zip(new["ssm"], state["ssm"])])

    monkeypatch.setattr(llm_families.GraniteHybridServing, "write_prompt",
                        write_prompt)
    cfg, params = tiny
    eng = LLMEngine(cfg, params, **ENGINE)
    try:
        prompts = _prompts(0, LENGTHS)
        outs = serve(eng, prompts, new=24)
        gaps = [family.reference_gap(p, o)[0].max()
                for p, o in zip(prompts, outs)]
        # (at these widths a state's trace in the logits is small: two of
        # the five reused streams leave the reference's greedy path, by
        # 0.004; the sound engine's streams above are held to 0.0)
        assert max(gaps[:4]) == 0.0
        assert max(gaps[4:]) > 1e-3
    finally:
        eng.shutdown()


def test_a_parked_and_an_empty_slot_keep_their_state(engine):
    """The decode program with a count of steps a slot: a slot given none
    (parked, or empty) keeps conv window and state bit for bit while its
    neighbour decodes; its token, position and length stay."""
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
    fixed = lambda s: jax.tree_util.tree_leaves(s["ssm"])  # noqa: E731
    # (layer 0's conv window holds its last three INPUTS, a function
    # of the tokens alone: a stream that repeats a token leaves it as
    # it was, so it is not asked to move)
    moved = [not np.array_equal(a[0], b[0])
             for a, b in zip(fixed(after), fixed(before))]
    assert all(moved[1:]), "the decoding slot's state did not advance"
    for a, b in zip(fixed(after), fixed(before)):
        np.testing.assert_array_equal(a[1:], b[1:])


def test_what_the_family_cannot_do_is_refused_in_words(engine):
    """A stream's state is pages AND fixed per-slot state: it cannot be
    handed to another engine (disaggregated prefill), nor carried by a
    drain snapshot."""
    from ray_tpu.serve.llm import _Prefilled
    from ray_tpu.serve.llm_families import family_of

    with pytest.raises(TypeError, match="GraniteHybridConfig.*a new family "
                                        "is a class"):
        family_of(object(), 64)
    for refused in (
            lambda: engine.submit_prefilled(
                _Prefilled([], 1, 4, 4, 0, [], True)),
            engine.snapshot_active_streams):
        with pytest.raises(NotImplementedError,
                           match="fixed per-slot state.*prefilled where"):
            refused()


def test_the_family_sizes_state_and_prefill_from_shapes():
    """At the published sizes: 36 layers of (64, 64, 128) float32 state
    and a (3, 4352) bfloat16 conv window a sequence, 8,192 bytes of K and
    V a token (four pools of 4 paired heads of 128), eight rows a batched
    prefill at every bucket the cell has."""
    import jax

    from ray_tpu.models.granite_hybrid import GRANITE_4_H_MICRO
    from ray_tpu.serve.llm_families import family_of

    fam = family_of(GRANITE_4_H_MICRO, 1600)
    assert fam.state_bytes_per_slot == 75_497_472 + 36 * 3 * 4352 * 2
    assert not fam.rewinds and not fam.portable_kv
    assert [fam.prefill_width(b, 64) for b in (64, 1024, 4096)] == [8, 8, 4]
    assert fam.prompt_pages(1024, 64) == 16
    state = jax.eval_shape(lambda: fam.init_state(2, 5, 64))
    assert [tuple(x.shape) for x in state["pools"][0]] == [(5, 4, 64, 128)] * 2
    assert len(state["pools"]) == 4 and len(state["ssm"]) == 36
    per_token = sum(x.size // 5 // 64 * x.dtype.itemsize
                    for x in jax.tree_util.tree_leaves(state["pools"]))
    assert per_token == 8192
