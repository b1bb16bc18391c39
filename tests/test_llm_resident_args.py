"""The decode chunk's arguments live on the device from chunk to chunk
(`LLMEngine._dev`): the program advances its own cursor, and the host sends
one of its numpy mirrors again only after it wrote to it in a way the
program did not (`LLMEngine._dirty`).  The mirrors stay the truth, so the
rule under test is one: AT EVERY DISPATCH the program is handed exactly
what the mirrors hold, whole arrays, empty slots too, which is what the
engine used to upload every chunk.  A dense tiny model (steps can be run
again: `rewinds`) and a hybrid one (they cannot: each slot's `steps` are
sized on the host, and sent when they differ from the chunk's before).
"""

import time

import numpy as np
import pytest

from ray_tpu.models.generate import SamplingParams
from ray_tpu.serve.llm import _MIRRORS, LLMEngine
from ray_tpu.util import tracing
from tests import tiny_families
from tests.tiny_families import LOOP_ENGINE as ENGINE, LOOP_K as K

# Where `decode_chunk_paged` takes each resident argument.
ARG_AT = {"token": 1, "pos": 2, "tables": 4, "lens": 5, "temps": 6,
          "top_ks": 7, "top_ps": 8, "chunk_no": 10, "steps": 11}
MODELS = {"dense": tiny_families.dense,
          "hybrid": tiny_families.granite_hybrid}


@pytest.fixture(params=list(MODELS))
def model(request):
    """A tiny model (`cfg`, `params`, `rewinds`), and whether a stream is
    what its plain reference decodes greedily from the prompt."""
    return MODELS[request.param]


def _prompt(seed, n):
    return tiny_families.prompts(seed, (n,), vocab=120)[0]


class Watch:
    """Stands where the engine calls its decode program: holds every
    resident argument of every dispatch against the host's mirror, counts
    how often each was sent anew, and may fail one call."""

    def __init__(self, eng, fail_at=0):
        self.eng, self.real, self.fail_at = eng, eng._decode_chunk_paged, \
            fail_at
        self.calls, self.wrong, self.empty_lens = 0, [], []
        self.sent = dict.fromkeys(ARG_AT, 0)
        self._last: dict = {}
        self._seen = {s["id"] for s in tracing.recent_spans()}
        eng._decode_chunk_paged = self

    def __call__(self, *args):
        self.calls += 1
        eng = self.eng
        for name, at in ARG_AT.items():
            if at >= len(args):         # (no `steps` where steps rewind)
                continue
            got, want = np.asarray(args[at]), getattr(eng, _MIRRORS[name])
            if not np.array_equal(got, want):
                self.wrong.append((self.calls, name, got.tolist(),
                                   want.tolist()))
            if args[at] is not self._last.get(name):
                self.sent[name] += 1
                self._last[name] = args[at]
        empty = [i for i, s in enumerate(eng._slots) if s.request is None]
        self.empty_lens.append(np.asarray(args[5])[empty].tolist())
        if self.calls == self.fail_at:
            raise RuntimeError("planted decode failure")
        return self.real(*args)

    def settled(self):
        """After the last walk (the engine quiesced): every copy on the
        device that carries no mark is its mirror; nothing was ever handed
        in that was not."""
        assert self.eng.quiesce_for_drain()
        eng = self.eng
        for name in set(eng._dev) - eng._dirty:
            np.testing.assert_array_equal(
                np.asarray(eng._dev[name]), getattr(eng, _MIRRORS[name]),
                err_msg=f"`{name}` differs from its mirror and has no mark")
        assert self.wrong == []
        eng.resume()

    def builds(self):
        """`uploaded` of this engine's decoding passes so far, in order."""
        return [s["attrs"]["uploaded"] for s in tracing.recent_spans()
                if s["id"] not in self._seen
                and s["name"] == "engine.decode.build"]


def test_an_admission_mid_stream(model):
    eng = LLMEngine(model.cfg, model.params, **ENGINE)
    try:
        watch = Watch(eng)
        first_p, second_p = _prompt(1, 9), _prompt(2, 23)
        first = eng.submit(first_p, SamplingParams(max_new_tokens=38))
        stream = iter(first)
        head = [next(stream) for _ in range(1 + 2 * K)]  # it is decoding
        second = eng.submit(second_p, SamplingParams(max_new_tokens=15))
        assert model.is_greedy(second_p, second.tokens())
        assert model.is_greedy(first_p, head + list(stream))
        watch.settled()
        # The admission sent the cursor and the tables again, the sampling
        # arrays not: both requests are greedy, as the empty slots were.
        assert watch.sent["tables"] >= 2
        assert watch.sent["temps"] == watch.sent["top_ks"] \
            == watch.sent["top_ps"] == 1
    finally:
        eng.shutdown()


@pytest.mark.parametrize("how", ["max_new_tokens", "eos_token"])
def test_a_stream_that_ends_mid_chunk(model, how):
    """Its device cursor ran on to the chunk's end; the slot's next chunk
    must see `lens` 0 and the dummy page, and the stream beside it nothing."""
    eng = LLMEngine(model.cfg, model.params, **ENGINE)
    try:
        watch = Watch(eng)
        short_p, long_p = _prompt(3, 12), _prompt(4, 30)
        whole = eng.generate(short_p, SamplingParams(max_new_tokens=14))
        assert model.is_greedy(short_p, whole)
        if how == "eos_token":
            # A token the stream first reads in the middle of a chunk
            # (token 0 is the prefill's, chunk c holds 1 + c K ... (c + 1) K).
            at = next(j for j in range(2, len(whole))
                      if whole[j] not in whole[:j] and j % K)
            sp = SamplingParams(max_new_tokens=14, eos_token=whole[at])
            want = whole[:at + 1]
        else:
            sp = SamplingParams(max_new_tokens=3 + K)   # 2 into a chunk
            want = whole[:3 + K]
        eng.quiesce_for_drain()
        short = eng.submit(short_p, sp)
        long = eng.submit(long_p, SamplingParams(max_new_tokens=33))
        eng.resume()
        assert short.tokens() == want
        assert model.is_greedy(long_p, long.tokens())
        watch.settled()
        assert (eng._lens == 0).all()
    finally:
        eng.shutdown()


def test_a_parked_consumer_that_comes_back(model):
    """`stream_buffer` 2: the slow stream's queue fills while nobody
    reads it. Where steps can be run again the host's cursor falls behind
    the device's at every park and is sent again; where they cannot, the
    slot is given no steps and the two never part."""
    eng = LLMEngine(model.cfg, model.params, stream_buffer=2, **ENGINE)
    try:
        watch = Watch(eng)
        slow_p, fast_p = _prompt(5, 7), _prompt(6, 19)
        slow = eng.submit(slow_p, SamplingParams(max_new_tokens=21))
        fast = eng.submit(fast_p, SamplingParams(max_new_tokens=21))
        stream = iter(fast)
        fast_out = []
        for tok in stream:                  # slow is not read meanwhile
            fast_out.append(tok)
            time.sleep(0.002)               # (a reader slower than a chunk)
        assert eng.report_metrics()["parked_events"] > 0
        time.sleep(0.1)
        assert model.is_greedy(slow_p, slow.tokens())
        assert model.is_greedy(fast_p, fast_out)
        watch.settled()
    finally:
        eng.shutdown()


def test_a_slot_used_by_a_second_request(model):
    eng = LLMEngine(model.cfg, model.params, **dict(ENGINE, max_batch=1))
    try:
        watch = Watch(eng)
        prompts = [_prompt(7, 20), _prompt(8, 3), _prompt(9, 33)]
        handles = [eng.submit(p, SamplingParams(max_new_tokens=n))
                   for p, n in zip(prompts, (14, 7, 10))]
        for p, h in zip(prompts, handles):
            assert model.is_greedy(p, h.tokens())
        watch.settled()
        assert watch.sent["tables"] >= 3
    finally:
        eng.shutdown()


def test_the_engine_recovers_from_a_failed_chunk(model):
    """The failed chunk may have taken the carry with it: everything is
    sent again from the mirrors, and the next stream is the reference's."""
    eng = LLMEngine(model.cfg, model.params, **ENGINE)
    try:
        watch = Watch(eng, fail_at=3)
        lost = eng.submit(_prompt(10, 11), SamplingParams(max_new_tokens=30))
        with pytest.raises(RuntimeError, match="planted decode failure"):
            lost.tokens()
        # (The consumer hears of the failure at once, the state is made
        # anew right after it: the pass has ended when the loop is quiet.)
        assert eng.quiesce_for_drain()
        assert eng._dirty == set(_MIRRORS) - {"steps"} and eng._dev == {}
        assert eng._steps is None
        eng.resume()
        prompts = [_prompt(11, 17), _prompt(12, 40)]
        handles = [eng.submit(p, SamplingParams(max_new_tokens=18))
                   for p in prompts]
        for p, h in zip(prompts, handles):
            assert model.is_greedy(p, h.tokens())
        watch.settled()
        # (the counter went on from where the host had it)
        assert int(eng._chunk_no) == watch.calls - 1 == int(
            np.asarray(eng._dev["chunk_no"]))
    finally:
        eng.shutdown()


def test_an_undisturbed_pass_uploads_nothing_and_an_empty_slot_stays_empty(
        model):
    """One stream of 22 chunks beside two empty slots: between its
    admission and its end no pass has anything to send (where the family
    cannot rewind, its steps are the chunk's before); the empty slots'
    `lens` on the
    device is 0 at every dispatch (it used to be re-sent as 0; a resident
    one that crept up would grow the kernel's work a chunk at a time)."""
    eng = LLMEngine(model.cfg, model.params, **ENGINE)
    try:
        watch = Watch(eng)
        before = eng.report_metrics()
        prompt = _prompt(13, 6)
        out = eng.generate(prompt, SamplingParams(max_new_tokens=1 + 22 * K))
        assert model.is_greedy(prompt, out)
        watch.settled()
        after = eng.report_metrics()
        assert watch.calls == 22
        assert watch.empty_lens == [[0, 0]] * 22
        # The first pass sends all eight (and the steps); no other sends
        # anything: the stream ends with its last chunk.
        assert watch.builds() == [8 if model.rewinds else 9] + [0] * 21
        assert after["decode_passes"] - before["decode_passes"] == 22
        assert after["decode_passes_clean"] \
            - before["decode_passes_clean"] == 21
        assert {k: watch.sent[k] for k in
                ("tables", "temps", "top_ks", "top_ps")} == {
            "tables": 1, "temps": 1, "top_ks": 1, "top_ps": 1}
    finally:
        eng.shutdown()


def test_a_sampling_request_beside_greedy_ones_resends_what_it_changed(
        model):
    """`temps` and `top_ks` once more, when the sampling request takes its
    slot, `top_ps` never (it asked for 1.0, which the slot had); the
    greedy streams are untouched by the keys drawn beside them."""
    eng = LLMEngine(model.cfg, model.params, **ENGINE)
    try:
        watch = Watch(eng)
        greedy_p = [_prompt(14, 10), _prompt(15, 26)]
        greedy = [eng.submit(p, SamplingParams(max_new_tokens=30))
                  for p in greedy_p]
        streams = [iter(h) for h in greedy]
        heads = [[next(s) for _ in range(1 + K)] for s in streams]
        drawn = eng.submit(_prompt(16, 8), SamplingParams(
            max_new_tokens=17, temperature=0.8, top_k=5)).tokens()
        assert len(drawn) == 17
        assert all(0 <= t < model.cfg.vocab_size for t in drawn)
        for p, head, s in zip(greedy_p, heads, streams):
            assert model.is_greedy(p, head + list(s))
        watch.settled()
        assert (watch.sent["temps"], watch.sent["top_ks"],
                watch.sent["top_ps"]) == (2, 2, 1)
    finally:
        eng.shutdown()
