"""Between the fetch of one decode chunk and the dispatch of the next the
engine's loop does only what that dispatch needs (`LLMEngine._loop`):

- an arrival that finds a slot empty is given it, and its prefill queued,
  BEHIND the chunk that is on the chip (`_admit_behind`), its first
  token fetched after that chunk's walk;
- a chunk's tokens are booked in the walk and handed to their streams, a
  stream's end with them, after the next chunk is dispatched (`_hand_off`).

Every test waits on events: a double stands where the engine calls its
decode program and holds a chunk's completion until the test lets go, so
"while the chunk is on the chip" is a state the test is in, not a race it
hopes to win.  A dense tiny model (steps can be run again) and a hybrid one
(they cannot), as in `test_llm_resident_args.py`, whose models these are.
The double and the fixtures are `tests/llm_loop_doubles.py`'s; this file
holds the admission's half, `tests/test_llm_hand_off.py` the hand-off's (one
file was the suite's longest chain).
"""

import pytest

from ray_tpu.models.generate import SamplingParams
from tests.llm_loop_doubles import (  # noqa: F401 (fixtures)
    K, WAIT, _end, _held, _prompt, _with_its_first_chunk_held, engine, model)


# ---- (a) admission under the chunk -----------------------------------------


def test_an_arrival_is_prefilled_behind_the_chunk_on_the_chip(model, engine):
    eng, hold = _held(engine)
    first_p, second_p = _prompt(21, 9), _prompt(22, 23)
    first, stream, head = _with_its_first_chunk_held(
        eng, hold, first_p, 30)
    before = eng.report_metrics()
    second = eng.submit(second_p, SamplingParams(max_new_tokens=9))
    # Its prefill is dispatched with the chunk still on the chip.
    assert hold.prefilled.wait(WAIT) and hold.held()
    assert eng.queue_depth() == 1 and eng.num_active() == 1
    hold.release()
    assert model.is_greedy(second_p, second.tokens())
    assert model.is_greedy(first_p, head + list(stream))
    after = eng.report_metrics()
    assert after["admissions"] - before["admissions"] == 1
    assert after["admissions_under_chunk"] \
        - before["admissions_under_chunk"] == 1
    # The wait for a slot ended, and the prefill began, before the held
    # chunk's one `engine.decode.wait` (begun at its fetch) had ended.
    held_wait = hold.spans("engine.decode.wait")[hold.held_call - 1]
    queue, = [s for s in hold.spans("request.queue")
              if s["rid"] == second.rid]
    admit, = [s for s in hold.spans("engine.admit")
              if second.rid in s["attrs"]["rids"]]
    prefill, = [s for s in hold.spans("engine.prefill")
                if s["parent"] == admit["id"]]
    assert admit["attrs"]["under_chunk"] is True
    assert admit["t0_ns"] <= _end(queue) <= prefill["t0_ns"] \
        <= _end(prefill) <= _end(admit) <= held_wait["t0_ns"]
    # Its first token was fetched after that chunk's walk, in the same pass.
    walk = next(s for s in hold.spans("engine.walk")
                if s["parent"] == held_wait["parent"])
    commit, = [s for s in hold.spans("engine.commit")
               if s["parent"] == held_wait["parent"]]
    fetch, = [s for s in hold.spans("engine.prefill.wait")
              if s["parent"] == commit["id"]]
    assert _end(walk) <= commit["t0_ns"] <= fetch["t0_ns"]
    assert commit["attrs"]["rows"] == 1
    # It joined the next chunk: one live slot at the held dispatch, two after.
    assert [(lens > 0).sum() for lens in
            hold.lens[hold.held_call - 1:hold.held_call + 1]] == [1, 2]


def test_nothing_is_watched_or_polled_when_no_slot_is_empty(model, engine):
    """Every slot taken at the dispatch: the loop blocks in the chunk's one
    fetch as it always did, with no `engine.chip.wait` before it; an
    arrival then waits for a walk to free a slot."""
    eng, hold = _held(engine, max_batch=1)
    first_p, second_p = _prompt(23, 14), _prompt(24, 6)
    first, stream, head = _with_its_first_chunk_held(
        eng, hold, first_p, 1 + 3 * K)
    second = eng.submit(second_p, SamplingParams(max_new_tokens=5))
    hold.release()
    assert model.is_greedy(first_p, head + list(stream))
    assert model.is_greedy(second_p, second.tokens())
    assert hold.spans("engine.chip.wait") == []
    assert eng.report_metrics()["admissions_under_chunk"] == 0
    assert all(s["attrs"]["under_chunk"] is False
               for s in hold.spans("engine.admit"))


def test_an_arrival_is_prefilled_behind_a_prefill_in_flight(model, engine):
    """After the chunk's walk the loop waits for the first token of what it
    admitted behind the chunk, with the chip at that prefill: an arrival
    then is admitted behind the prefill as it would be behind a chunk, and
    committed in the same pass."""
    eng, hold = _held(engine)
    prompts = [_prompt(60, 9), _prompt(61, 23), _prompt(62, 14)]
    # (the two prefills fit beside each other: see the next test)
    eng._prefill_bytes.update({(1, 16): 40, (1, 32): 60, (3, 32): 100})
    first, stream, head = _with_its_first_chunk_held(
        eng, hold, prompts[0], 30)
    hold.hold_prefills = True
    second = eng.submit(prompts[1], SamplingParams(max_new_tokens=9))
    assert hold.prefilled.wait(WAIT)
    hold.hold_prefills = False
    hold.gates[0].set()                     # the chunk is done
    behind, = hold.prefills_held
    assert behind.watched.wait(WAIT)        # the loop waits behind `second`
    hold.prefilled.clear()
    third = eng.submit(prompts[2], SamplingParams(max_new_tokens=7))
    assert hold.prefilled.wait(WAIT) and hold.held()
    hold.release()
    for p, out in zip(prompts, (head + list(stream), second.tokens(),
                                third.tokens())):
        assert model.is_greedy(p, out)
    assert eng.report_metrics()["admissions_under_chunk"] == 2
    by_id = {s["id"]: s for s in hold.spans()}
    admit, = [s for s in hold.spans("engine.admit")
              if third.rid in s["attrs"]["rids"]]
    commit = by_id[admit["parent"]]
    assert admit["attrs"]["under_chunk"] is True
    assert commit["name"] == "engine.commit" and commit["attrs"]["rows"] == 2
    assert by_id[commit["parent"]]["name"] == "engine.pass"


@pytest.mark.parametrize("fits", [True, False])
def test_prefills_in_flight_hold_no_more_than_one_prefill_has(
        model, engine, fits):
    """A prefill's outputs are allocated when it is dispatched: what is in
    flight behind a chunk may hold together what the largest single
    dispatch holds, and no more. With the programs' sizes planted: two
    arrivals under one chunk are both admitted behind it where they fit
    together, and the second waits for the top of the next pass where
    they do not."""
    eng, hold = _held(engine)
    prompts = [_prompt(63, 9), _prompt(64, 23), _prompt(65, 14)]
    eng._prefill_bytes.update(
        {(1, 16): 40, (1, 32): 60, (3, 32): 100 if fits else 99})
    first, stream, head = _with_its_first_chunk_held(
        eng, hold, prompts[0], 30)
    second = eng.submit(prompts[1], SamplingParams(max_new_tokens=9))
    assert hold.prefilled.wait(WAIT)
    third = eng.submit(prompts[2], SamplingParams(max_new_tokens=7))
    if fits:
        hold.prefilled.clear()
        assert hold.prefilled.wait(WAIT) and hold.held()
    hold.release()
    for p, out in zip(prompts, (head + list(stream), second.tokens(),
                                third.tokens())):
        assert model.is_greedy(p, out)
    by_rid = {rid: s["attrs"]["under_chunk"]
              for s in hold.spans("engine.admit")
              for rid in s["attrs"]["rids"]}
    assert by_rid == {first.rid: False, second.rid: True, third.rid: fits}


# ---- (b) greedy tokens, however a request came in --------------------------


def test_greedy_streams_whichever_way_a_request_was_admitted(model, engine):
    """One request with nothing on the chip, one behind a chunk, one into a
    slot that a walk freed while it waited: each is the reference's."""
    eng, hold = _held(engine, max_batch=2)
    prompts = [_prompt(25, 11), _prompt(26, 40), _prompt(27, 5)]
    idle, stream, head = _with_its_first_chunk_held(
        eng, hold, prompts[0], 19)
    under = eng.submit(prompts[1], SamplingParams(max_new_tokens=22))
    assert hold.prefilled.wait(WAIT)
    # Both slots are spoken for: this one waits for a walk to free one.
    freed = eng.submit(prompts[2], SamplingParams(max_new_tokens=13))
    hold.release()
    assert model.is_greedy(prompts[0], head + list(stream))
    assert model.is_greedy(prompts[1], under.tokens())
    assert model.is_greedy(prompts[2], freed.tokens())
    got = eng.report_metrics()
    assert (got["admissions"], got["admissions_under_chunk"]) == (3, 1)
    by_rid = {rid: s["attrs"]["under_chunk"]
              for s in hold.spans("engine.admit")
              for rid in s["attrs"]["rids"]}
    assert by_rid == {idle.rid: False, under.rid: True, freed.rid: False}


# ---- (c) a failed chunk with an admission in flight ------------------------


def test_a_failed_chunk_fails_the_admission_in_flight(model, engine):
    eng, hold = _held(engine)
    pages = eng._alloc.free_pages
    first, stream, _ = _with_its_first_chunk_held(
        eng, hold, _prompt(28, 10), 30)
    second = eng.submit(_prompt(29, 21), SamplingParams(max_new_tokens=8))
    assert hold.prefilled.wait(WAIT)
    assert eng._alloc.free_pages < pages
    hold.release(error=RuntimeError("planted chunk failure"))
    with pytest.raises(RuntimeError, match="planted chunk failure"):
        second.tokens()     # in no slot yet, and failed all the same
    with pytest.raises(RuntimeError, match="planted chunk failure"):
        list(stream)
    assert eng.quiesce_for_drain()
    assert eng._alloc.free_pages == pages
    assert eng._in_flight == [] and eng.queue_depth() == 0
    eng.resume()
    hold.error = None
    again_p = _prompt(30, 17)
    assert model.is_greedy(
        again_p, eng.generate(again_p, SamplingParams(max_new_tokens=12)))


def test_a_failed_first_token_fetch_fails_its_group_alone(model, engine):
    eng, hold = _held(engine)
    pages = eng._alloc.free_pages
    first_p = _prompt(31, 10)
    first, stream, head = _with_its_first_chunk_held(
        eng, hold, first_p, 21)
    real_sample = eng._sample

    class Lost:
        def __array__(self, *args, **kwargs):
            raise RuntimeError("planted prefill failure")

    def sample(*args):
        real_sample(*args)
        return Lost()

    eng._sample = sample
    second = eng.submit(_prompt(32, 21), SamplingParams(max_new_tokens=8))
    assert hold.prefilled.wait(WAIT)
    eng._sample = real_sample
    hold.release()
    with pytest.raises(RuntimeError, match="planted prefill failure"):
        second.tokens()
    assert model.is_greedy(first_p, head + list(stream))
    assert eng.quiesce_for_drain()
    assert eng._alloc.free_pages == pages
    eng.resume()
