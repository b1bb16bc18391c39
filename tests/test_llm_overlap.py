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
"""

import sys
import threading
from collections import deque

import numpy as np
import pytest

from ray_tpu.models.generate import SamplingParams
from ray_tpu.serve.llm import LLMEngine, RequestHandle, _Prefilled
from ray_tpu.util import tracing
from tests.test_llm_resident_args import (  # noqa: F401 (fixtures)
    ENGINE, K, _prompt, dense, hybrid, model)

# An event that has not come after this long has failed the test.
WAIT = 120.0


class Held:
    """A chunk's tokens whose completion the test holds: the watcher's
    `block_until_ready` and the loop's fetch wait for the gate, and raise
    what the test planted."""

    def __init__(self, toks, gate, hold):
        self._toks, self._gate, self._hold = toks, gate, hold
        self.watched = threading.Event()    # the loop waits behind it

    def _wait(self):
        self.watched.set()
        assert self._gate.wait(WAIT)
        if self._hold.error is not None:
            raise self._hold.error

    def block_until_ready(self):
        self._wait()
        return self

    def __array__(self, *args, **kwargs):
        self._wait()
        return np.asarray(self._toks)


class Hold:
    """Stands where the engine calls its decode program (and where it
    prefills a group of admissions): counts the chunks, keeps each one's
    `lens` and every watched stream's backlog at its dispatch, and holds
    the completion of the chunks the test asks for."""

    def __init__(self, eng):
        self.eng, self.real = eng, eng._decode_chunk_paged
        self.streams: list = []
        self.calls, self.lens, self.backlogs = 0, [], []
        self.gates, self.error, self.held_call = [], None, 0
        self._armed = None
        self.dispatched = threading.Event()     # the held chunk is on
        self.prefilled = threading.Event()      # a group was dispatched
        self._seen = {s["id"] for s in tracing.recent_spans()}
        eng._decode_chunk_paged = self
        admit_group = eng._admit_paged_group

        def admit(*args):
            admit_group(*args)
            self.prefilled.set()

        eng._admit_paged_group = admit
        # (asked for by a test: a prefill's first tokens held as well)
        self.prefills_held: list = []
        self.hold_prefills = False
        real_sample = eng._sample

        def sample(*args):
            toks = real_sample(*args)
            if not self.hold_prefills:
                return toks
            self.gates.append(threading.Event())
            self.prefills_held.append(Held(toks, self.gates[-1], self))
            return self.prefills_held[-1]

        eng._sample = sample

    def __call__(self, *args):
        self.calls += 1
        self.lens.append(np.asarray(args[5]).copy())
        self.backlogs.append([len(h._handed) + len(h._booked)
                              for h in self.streams])
        out = self.real(*args)
        if self._armed is None:
            return out
        gate, self._armed, self.held_call = self._armed, None, self.calls
        self.dispatched.set()
        return (Held(out[0], gate, self), *out[1:])

    def arm(self):
        """The next chunk dispatched stays on the chip until its gate
        (returned) is set, or `release`."""
        gate = threading.Event()
        self.gates.append(gate)
        self.dispatched.clear()
        self.prefilled.clear()
        self._armed = gate
        return gate

    def release(self, error=None):
        self.error = error
        for gate in self.gates:
            gate.set()

    def held(self):
        return any(not gate.is_set() for gate in self.gates)

    def spans(self, name=None):
        return [s for s in tracing.recent_spans()
                if s["id"] not in self._seen
                and (name is None or s["name"] == name)]


def _end(span):
    return span["t0_ns"] + span["dur_ns"]


def _with_its_first_chunk_held(eng, hold, prompt, max_new):
    """Submit a stream whose first chunk stays on the chip: (handle, its
    iterator, its first token).  The first token is handed over after that
    chunk's dispatch, so when it is here the chunk is on, and held: no
    race between the test and the loop."""
    hold.arm()
    handle = eng.submit(prompt, SamplingParams(max_new_tokens=max_new))
    stream = iter(handle)
    head = [next(stream)]
    assert hold.dispatched.is_set() and hold.held()
    hold.prefilled.clear()              # (set by this stream's own group)
    return handle, stream, head


@pytest.fixture
def engine(model):
    made = []

    def make(**over):
        made.append(LLMEngine(model.cfg, model.params,
                              **dict(ENGINE, **over)))
        return made[-1]

    yield make
    for eng in made:
        if "hold" in eng.__dict__:
            eng.hold.release()
        eng.shutdown()


def _held(engine, **over):
    eng = engine(**over)
    eng.hold = Hold(eng)
    return eng, eng.hold


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


# ---- (d) the hand-off, and a stream's end in it ----------------------------


def test_a_handle_books_then_hands_over_whole_with_its_end():
    handle = RequestHandle(3, SamplingParams(max_new_tokens=3),
                           max_buffered=4)
    got, done = [], threading.Event()

    def consume():
        got.extend(handle)
        done.set()

    threading.Thread(target=consume, daemon=True).start()
    assert all(handle._offer(t) for t in (7, 8, 9))
    # Booked is not handed over: the consumer has nothing, the bound has.
    assert got == [] and not handle._handed and handle.room() == 1
    assert handle._hand_over(end=True) == 3
    # Nothing polls: the end came with the tokens and woke the consumer.
    assert done.wait(WAIT) and got == [7, 8, 9]
    assert handle.room() == 4


def test_a_handles_bound_counts_what_is_booked_and_not_handed_over():
    handle = RequestHandle(3, SamplingParams(max_new_tokens=9),
                           max_buffered=4)
    assert [handle._offer(t) for t in range(5)] == [True] * 4 + [False]
    assert handle.backlog_full() and handle.room() == 0
    assert handle._hand_over() == 4         # (nothing ended)
    assert handle.backlog_full()            # handed over, not yet taken
    stream = iter(handle)
    assert [next(stream), next(stream)] == [0, 1]
    assert handle.room() == 2 and handle._offer(5) and handle.room() == 1
    handle._finish(RuntimeError("gone"))
    assert [next(stream), next(stream), next(stream)] == [2, 3, 5]
    with pytest.raises(RuntimeError, match="gone"):
        next(stream)


def test_a_blocked_consumer_returns_on_the_hand_off_that_ends_it(
        model, engine):
    """A stream's last chunk is held on the chip with its consumer blocked
    in the iterator: the consumer returns when the loop hands that chunk
    over, after it has dispatched the OTHER stream's next chunk, and the
    hand-off says it carried an end."""
    eng, hold = _held(engine, max_batch=2)
    short_p, long_p = _prompt(33, 8), _prompt(34, 15)
    _, long_stream, long_head = _with_its_first_chunk_held(
        eng, hold, long_p, 1 + 6 * K)
    behind = hold.gates[-1]
    short = eng.submit(short_p, SamplingParams(max_new_tokens=1 + K))
    assert hold.prefilled.wait(WAIT)
    hold.arm()                      # the chunk that ends `short`
    behind.set()
    short_stream = iter(short)
    first = next(short_stream)      # handed over after that dispatch
    assert hold.dispatched.wait(WAIT)
    rest, done = [], threading.Event()

    def consume():
        rest.extend(short_stream)
        done.set()

    threading.Thread(target=consume, daemon=True).start()
    assert not done.is_set() and short._handed == deque()
    hold.release()
    assert done.wait(WAIT)
    assert model.is_greedy(short_p, [first] + rest)
    assert model.is_greedy(long_p, long_head + list(long_stream))
    ended = [s for s in hold.spans("engine.handoff") if s["attrs"]["ended"]]
    assert [s["attrs"]["ended"] for s in ended] == [1, 1]
    # The one that ended `short` stands after the next chunk's dispatch.
    dispatches = hold.spans("engine.decode.dispatch")
    assert _end(dispatches[hold.held_call]) <= ended[0]["t0_ns"]
    assert sum(s["attrs"]["tokens"] for s in hold.spans("engine.handoff")) \
        == 2 + 7 * K


def test_what_is_booked_is_handed_over_before_an_idle_wait(model, engine):
    """The last stream ends: no chunk follows, so its tokens and its end
    are handed over at once, outside any dispatch."""
    eng, hold = _held(engine)
    prompt = _prompt(35, 12)
    out = eng.generate(prompt, SamplingParams(max_new_tokens=2 + K))
    assert model.is_greedy(prompt, out)
    assert eng.quiesce_for_drain()
    assert eng._booked == {}
    last = hold.spans("engine.handoff")[-1]
    assert last["attrs"] == {"streams": 1, "tokens": 1, "ended": 1}
    assert hold.calls == 2 and len(hold.spans("engine.handoff")) == 3
    eng.resume()


# ---- (e) parking ------------------------------------------------------------


def test_a_full_stream_parks_with_booked_tokens_counted(model, engine):
    """`stream_buffer` 3 and a consumer that does not read: the stream
    never holds more than 3 tokens, booked and handed over together, at
    any dispatch; it parks, and read later it has lost nothing."""
    eng, hold = _held(engine, stream_buffer=3)
    slow_p, fast_p = _prompt(36, 7), _prompt(37, 19)
    slow = eng.submit(slow_p, SamplingParams(max_new_tokens=17))
    hold.streams.append(slow)
    fast = eng.submit(fast_p, SamplingParams(max_new_tokens=25))
    fast_out = []
    for tok in fast:                    # `slow` is not read meanwhile
        fast_out.append(tok)
    assert eng.report_metrics()["parked_events"] > 0
    assert slow.backlog_full() and hold.calls >= 6
    assert max(b[0] for b in hold.backlogs if b) == 3
    assert model.is_greedy(fast_p, fast_out)
    assert model.is_greedy(slow_p, slow.tokens())
    if not model.rewinds:
        # Its steps were sized by the room that booked tokens had left: no
        # offer of the walk was ever refused (a step could not be re-run).
        walks = [s["attrs"] for s in hold.spans("engine.walk")]
        assert sum(w["parked"] for w in walks) == 0
        assert sum(w["emitted"] for w in walks) == 17 + 25 - 2


# ---- (f) first come, first served ------------------------------------------


def test_nothing_is_taken_past_a_request_that_waits_for_pages(model, engine):
    """The pool holds the first request and not the second, which is
    deferred; a third that WOULD fit arrives while a chunk is on the chip
    and is not admitted behind it, nor before the second."""
    eng, hold = _held(engine, kv_pool_tokens=96)        # six pages
    prompts = [_prompt(38, 20), _prompt(39, 30), _prompt(40, 5)]
    first, stream, head = _with_its_first_chunk_held(
        eng, hold, prompts[0], 40)
    second = eng.submit(prompts[1], SamplingParams(max_new_tokens=20))
    third = eng.submit(prompts[2], SamplingParams(max_new_tokens=8))
    hold.release()
    assert model.is_greedy(prompts[0], head + list(stream))
    assert model.is_greedy(prompts[1], second.tokens())
    assert model.is_greedy(prompts[2], third.tokens())
    assert eng.report_metrics()["admissions_under_chunk"] == 0
    queued = {s["rid"]: s for s in hold.spans("request.queue")}
    assert queued[second.rid]["attrs"]["deferred"] is True
    assert _end(queued[second.rid]) <= _end(queued[third.rid])


@pytest.mark.parametrize("model", ["dense"], indirect=True)
def test_nothing_is_taken_past_a_prefilled_pack(dense, engine):
    """(A dense stream alone has a K/V prefix to hand in.)"""
    from ray_tpu.serve.llm_disagg import PrefillEngine

    eng, hold = _held(engine)
    prompts = [_prompt(41, 9), _prompt(42, 14), _prompt(43, 6)]
    sp = SamplingParams(max_new_tokens=11)
    out = PrefillEngine(dense.cfg, dense.params, max_len=128).prefill(
        np.asarray(prompts[1]), sp)
    pack = _Prefilled(out["kv"], out["first_token"], out["prompt_len"],
                      out["kv_len"], 0, [], emit_first=True)
    first, stream, head = _with_its_first_chunk_held(
        eng, hold, prompts[0], 30)
    packed = eng.submit_prefilled(pack, sp)
    plain = eng.submit(prompts[2], sp)          # behind the pack
    hold.release()
    assert dense.is_greedy(prompts[0], head + list(stream))
    assert dense.is_greedy(prompts[1], packed.tokens())
    assert dense.is_greedy(prompts[2], plain.tokens())
    assert eng.report_metrics()["admissions_under_chunk"] == 0
    admit, = [s for s in hold.spans("engine.admit")
              if s["attrs"]["admitted"] == 2]
    assert admit["attrs"]["rids"] == [packed.rid, plain.rid]
    assert admit["attrs"]["under_chunk"] is False


# ---- (g) the spans the readers count on ------------------------------------


def test_one_decode_wait_a_chunk_and_no_two_waits_overlap(model, engine):
    eng, hold = _held(engine, max_batch=2)
    prompts = [_prompt(44, 11), _prompt(45, 33), _prompt(46, 5),
               _prompt(47, 18)]
    first, stream, head = _with_its_first_chunk_held(
        eng, hold, prompts[0], 26)
    under = eng.submit(prompts[1], SamplingParams(max_new_tokens=9))
    assert hold.prefilled.wait(WAIT)
    waiting = [eng.submit(p, SamplingParams(max_new_tokens=7))
               for p in prompts[2:]]
    before = eng.report_metrics()
    hold.release()
    outs = [head + list(stream), under.tokens()] + \
        [h.tokens() for h in waiting]
    for p, out in zip(prompts, outs):
        assert model.is_greedy(p, out)
    assert eng.quiesce_for_drain()
    after = eng.report_metrics()
    spans = hold.spans()
    waits = sorted((s for s in spans if s["name"].endswith(".wait")),
                   key=lambda s: s["t0_ns"])
    assert {s["name"] for s in waits} == {
        "engine.chip.wait", "engine.decode.wait", "engine.prefill.wait"}
    assert {s["thread"] for s in waits} == {"llm-engine"}
    for earlier, later in zip(waits, waits[1:]):
        assert _end(earlier) <= later["t0_ns"]
    # One `engine.decode.wait` a chunk, each in a pass of its own, and the
    # counts they carry are the engine's.
    chunks = [s for s in waits if s["name"] == "engine.decode.wait"]
    assert len(chunks) == hold.calls == after["decode_passes"]
    assert len({s["parent"] for s in chunks}) == len(chunks)
    assert sum(s["attrs"]["pages_live"] for s in chunks) \
        == after["paged_pages_live"]
    assert sum(s["attrs"]["steps"] for s in chunks) \
        == after["state_slot_steps"]
    assert before["decode_passes"] == hold.held_call
    # Every admission is a descendant of a pass; every prefill of an
    # admission.
    by_id = {s["id"]: s for s in spans}
    for s in spans:
        if s["name"] == "engine.admit":
            above = by_id[s["parent"]]
            assert above["name"] == "engine.pass" or (
                above["name"] == "engine.commit"
                and by_id[above["parent"]]["name"] == "engine.pass")
        if s["name"] == "engine.prefill":
            assert by_id[s["parent"]]["name"] == "engine.admit"
    eng.resume()


# ---- the shared state under many threads -----------------------------------


def test_many_clients_in_a_closed_loop_lose_and_mix_nothing(model, engine):
    """Twelve clients on three slots with the interpreter switching threads
    every 10 us: arrivals while a chunk is on the chip, hand-offs and ends
    interleave every way they can; every stream is still its own, whole,
    and the engine ends with nothing booked, in flight or reserved."""
    eng = engine()
    prompts = [_prompt(50 + i, n) for i, n in enumerate((5, 17, 33, 9))]
    sps = [SamplingParams(max_new_tokens=n) for n in (6, 11, 3, 14)]
    want = [eng.generate(p, sp) for p, sp in zip(prompts, sps)]
    for p, out in zip(prompts, want):
        assert model.is_greedy(p, out)
    pages, wrong = eng._alloc.free_pages, []

    def client(i):
        try:
            for j in range(5):
                k = (i + j) % 4
                got = eng.generate(prompts[k], sps[k])
                if got != want[k]:
                    wrong.append((i, j, got))
        except Exception as e:  # noqa: BLE001 (reported below)
            wrong.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        clients = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(12)]
        for t in clients:
            t.start()
        for t in clients:
            t.join(WAIT)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert wrong == []
    assert eng.quiesce_for_drain()
    assert eng.report_metrics()["admissions"] == 4 + 12 * 5
    assert eng._booked == {} and eng._in_flight == []
    assert eng.queue_depth() == 0 and eng._alloc.free_pages == pages
    eng.resume()
