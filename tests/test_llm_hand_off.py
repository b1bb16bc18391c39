"""The other half of `test_llm_overlap.py`'s subject (`LLMEngine._loop`
between the fetch of one decode chunk and the dispatch of the next): a
chunk's tokens are booked in the walk and handed to their streams, a
stream's end with them, after the next chunk is dispatched (`_hand_off`);
what that does to parking, to first come first served and to the spans the
benchmark's readers count on; and the shared state under many threads.
The doubles are `tests/llm_loop_doubles.py`'s: every test waits on events.
"""

import sys
import threading
from collections import deque

import numpy as np
import pytest

from ray_tpu.models.generate import SamplingParams
from ray_tpu.serve.llm import RequestHandle, _Prefilled
from tests.llm_loop_doubles import (  # noqa: F401 (fixtures)
    K, WAIT, _end, _held, _prompt, _with_its_first_chunk_held, dense, engine,
    model)


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
