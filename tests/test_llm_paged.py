"""The engine's pages: admission bounded by POOL pages (resident
tokens), not slot count, pages recycled as streams leave, the batched
prefill, and what the kernel's counters read; greedy output bit-equal to
the one-shot Generator throughout (the plain cases of that are in
tests/test_serve_llm.py)."""

import contextlib

import numpy as np
import pytest

from ray_tpu.models.generate import SamplingParams
from ray_tpu.serve.llm import LLMEngine
from tests.tiny_families import dense


@pytest.fixture(scope="module")
def tiny_model():
    return dense.cfg, dense.params


def _reference_greedy(cfg, params, prompt, n_new):
    return dense.greedy(prompt, n_new)


@contextlib.contextmanager
def _page_writes(eng):
    """What `eng` hands its page writer, a call: the shapes of the fresh
    K/V leaves and the page columns."""
    seen = []
    real = eng._write_prompt_pages

    def spy(pools, fresh, slots, page_ids):
        seen.append(({x.shape for kv in fresh for x in kv},
                     np.asarray(page_ids)))
        return real(pools, fresh, slots, page_ids)

    eng._write_prompt_pages = spy
    try:
        yield seen
    finally:
        eng._write_prompt_pages = real


def _engine(tiny_model, slots):
    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, max_batch=slots, max_len=96, page_size=16)
    yield eng
    eng.shutdown()


# One engine a module for the tests that submit to it, read what comes
# back and leave it idle (a double one of them plants it takes away again).
@pytest.fixture(scope="module")
def two_slots(tiny_model):
    yield from _engine(tiny_model, 2)


@pytest.fixture(scope="module")
def four_slots(tiny_model):
    yield from _engine(tiny_model, 4)


def test_paged_admission_bounded_by_pool_not_slots(tiny_model):
    """Pool holds pages for ~1.5 requests even though 3 slots exist:
    requests queue on POOL capacity and all complete once earlier
    streams free their pages."""
    cfg, params = tiny_model
    # Each request: prompt 4 + max_new 8 + chunk 4 = 16 tokens = 1 page
    # of 16... use page_size 16, pool of 2 pages -> one resident request
    # at a time (request needs 16 tokens = 1 page; pool_tokens=32 gives
    # 2 pages, but need includes chunk overshoot -> 1 page each).
    eng = LLMEngine(cfg, params, max_batch=3, max_len=96, page_size=16,
                    decode_chunk=4, kv_pool_tokens=32)
    try:
        prompts = [[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12]]
        expected = [_reference_greedy(cfg, params, p, 8) for p in prompts]
        handles = [eng.submit(p, SamplingParams(max_new_tokens=8))
                   for p in prompts]
        assert [h.tokens() for h in handles] == expected
        # Every page returned to the pool after completion.
        assert eng._alloc.free_pages == eng._alloc.num_pages - 1  # - dummy
    finally:
        eng.shutdown()


def test_paged_pool_capacity_rejects_oversized_request(tiny_model):
    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, max_batch=2, max_len=96, page_size=16,
                    kv_pool_tokens=32)
    try:
        with pytest.raises(ValueError, match="KV pages"):
            eng.submit(list(range(1, 40)), SamplingParams(max_new_tokens=40))
    finally:
        eng.shutdown()


def test_paged_pages_freed_on_completion(two_slots):
    eng = two_slots
    assert eng.quiesce_for_drain()
    baseline = eng._alloc.free_pages
    eng.resume()
    out = eng.generate([3, 1, 4], SamplingParams(max_new_tokens=6))
    assert len(out) == 6
    assert eng.quiesce_for_drain()
    assert eng._alloc.free_pages == baseline
    eng.resume()


def test_batched_prefill_used_and_bit_equal(tiny_model, four_slots):
    """A burst of same-bucket requests must go through the fixed-width
    prefill_many program (one dispatch for the group) AND stay greedy
    bit-equal to the one-shot Generator — batched rows may not perturb
    single-sequence numerics."""
    cfg, params = tiny_model
    eng = four_slots
    calls = {"many": 0, "one": 0}
    real_many, real_one = eng._prefill_many, eng._prefill_one

    def spy_many(*a, **k):
        calls["many"] += 1
        return real_many(*a, **k)

    def spy_one(*a, **k):
        calls["one"] += 1
        return real_one(*a, **k)

    eng._prefill_many, eng._prefill_one = spy_many, spy_one
    try:
        # Same bucket (lengths 3-5 pad to one bucket of >= page_size).
        prompts = [[1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11, 12], [13, 14, 15]]
        expected = [_reference_greedy(cfg, params, p, 8) for p in prompts]
        handles = [eng.submit(p, SamplingParams(max_new_tokens=8))
                   for p in prompts]
        assert [h.tokens() for h in handles] == expected
        assert calls["many"] >= 1, (
            "burst of same-bucket admissions never used the batched "
            f"prefill program (calls={calls})")
    finally:
        eng._prefill_many, eng._prefill_one = real_many, real_one


@pytest.mark.parametrize("prompt_len, bucket",
                         [(5, 16), (16, 16), (17, 32), (40, 64), (70, 96)],
                         ids=["in-a-page", "a-page-full", "two-pages",
                              "mid", "max_len"])
def test_prefill_hands_over_kv_as_long_as_the_bucket(tiny_model, two_slots,
                                                     prompt_len, bucket):
    """A prompt attends over itself, so what its prefill hands the page
    writer is K/V of the bucket's length with as many page columns, and
    not a cache of `max_len` (6 columns here, whatever the bucket): a
    bucket of one page, a prompt that fills its page to the last row, the
    bucket that is `max_len` itself and no power of two. The greedy
    stream is the Generator's, as it was over the dense cache."""
    cfg, params = tiny_model
    eng = two_slots
    with _page_writes(eng) as seen:
        prompt = [(i * 11 + 5) % 120 + 1 for i in range(prompt_len)]
        n_new = min(12, 96 - prompt_len)
        assert eng.generate(prompt, SamplingParams(max_new_tokens=n_new)) \
            == _reference_greedy(cfg, params, prompt, n_new)
    (shapes, page_ids), = seen
    assert shapes == {(1, cfg.n_kv_heads, bucket, cfg.head_dim)}
    assert page_ids.shape == (1, bucket // 16)


def test_batched_prefill_of_unequal_rows_fills_only_their_own_pages(
        tiny_model, four_slots):
    """Rows of one bucket and unequal length through `prefill_many`: a
    row's pages past its prompt are the dummy page's columns, a padding
    row's all of them, and every stream is the Generator's."""
    cfg, params = tiny_model
    eng = four_slots
    dummy = eng._dummy_page
    with _page_writes(eng) as seen:
        prompts = [[(i * 7 + r) % 120 + 1 for i in range(n)]
                   for r, n in enumerate((33, 64, 50))]
        expected = [_reference_greedy(cfg, params, p, 8) for p in prompts]
        eng.quiesce_for_drain()
        handles = [eng.submit(p, SamplingParams(max_new_tokens=8))
                   for p in prompts]
        eng.resume()
        assert [h.tokens() for h in handles] == expected
    (shapes, page_ids), = seen
    assert shapes == {(4, cfg.n_kv_heads, 64, cfg.head_dim)}
    assert [(row != dummy).sum() for row in page_ids] == [3, 4, 4, 0]


def test_freed_slot_has_length_zero_and_live_pages_are_counted(tiny_model):
    """A slot whose long stream left tells the kernel so (`_lens == 0`:
    it costs the one dummy page, not the stream's pages), the stream
    beside it goes on bit-equal to the Generator, and the engine's
    counters read the table pages the kernel had to visit against the
    pages the tables hold, for lengths known chunk by chunk."""
    cfg, params = tiny_model
    K, page, slots = 4, 16, 3
    eng = LLMEngine(cfg, params, max_batch=slots, max_len=96,
                    page_size=page, decode_chunk=K)
    try:
        long_prompt = [1 + i % 100 for i in range(40)]
        short_prompt = [7, 3, 9, 2, 5]
        # Admitted together (the loop is paused while both are submitted):
        # the long stream's 5 tokens end with chunk 0, the short one's 21
        # with chunk 4, and the third slot is never used.
        assert eng.quiesce_for_drain()
        long_h = eng.submit(long_prompt, SamplingParams(max_new_tokens=1 + K))
        short_h = eng.submit(short_prompt,
                             SamplingParams(max_new_tokens=1 + 5 * K))
        eng.resume()
        assert long_h.tokens() == _reference_greedy(
            cfg, params, long_prompt, 1 + K)
        assert eng.quiesce_for_drain()
        assert eng._lens[0] == 0
        assert (eng._tables[0] == eng._dummy_page).all()
        eng.resume()
        assert short_h.tokens() == _reference_greedy(
            cfg, params, short_prompt, 1 + 5 * K)
        assert eng.quiesce_for_drain()
        assert (eng._lens == 0).all()

        lens_by_chunk = [[40, 5, 0]] + [[0, 5 + K * c, 0] for c in (1, 2, 3, 4)]
        live = sum(-(-(n + k) // page)
                   for lens in lens_by_chunk for n in lens
                   for k in range(1, K + 1))
        table = len(lens_by_chunk) * K * slots * eng._np_pages
        got = eng.report_metrics()
        assert (got["paged_pages_live"], got["paged_pages_table"]) \
            == (live, table)
        assert got["paged_live_share"] == pytest.approx(live / table)
    finally:
        eng.shutdown()


def test_one_slot_used_again_and_again(tiny_model):
    """Three requests through the one slot of an engine, none a multiple
    of the chunk long: each stream's last chunk runs past its end into
    pages that go back to the pool, and the next stream takes slot and
    pages over. Every stream is the Generator's."""
    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, max_batch=1, max_len=96, page_size=16,
                    decode_chunk=4, kv_pool_tokens=64)
    try:
        prompts = [[1 + (3 * i) % 90 for i in range(20)], [5, 6, 7],
                   [9 + i for i in range(33)]]
        handles = [eng.submit(p, SamplingParams(max_new_tokens=n))
                   for p, n in zip(prompts, (14, 7, 10))]
        for p, n, h in zip(prompts, (14, 7, 10), handles):
            assert h.tokens() == _reference_greedy(cfg, params, p, n)
        assert eng._alloc.free_pages == eng._num_pages - 1
    finally:
        eng.shutdown()


def test_a_rerun_chunk_overwrites_what_it_wrote(tiny_model):
    """A consumer that stops reading fills its queue; the engine drops
    the steps it could not hand over and runs them again from the
    committed position (`LlamaServing.rewinds`). The kernel writes a
    rerun step's K/V over the uncommitted ones before reading them, so
    the stream, and the stream beside it, stay the Generator's."""
    import time

    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, max_batch=2, max_len=96, page_size=16,
                    decode_chunk=4, stream_buffer=3)
    try:
        slow_p, fast_p = [2, 4, 6, 8, 10, 12, 14], [3 + i for i in range(19)]
        slow = eng.submit(slow_p, SamplingParams(max_new_tokens=26))
        fast = eng.submit(fast_p, SamplingParams(max_new_tokens=26))
        fast_out = fast.tokens()            # slow is not read meanwhile
        assert eng.report_metrics()["parked_events"] > 0
        time.sleep(0.2)
        assert slow.tokens() == _reference_greedy(cfg, params, slow_p, 26)
        assert fast_out == _reference_greedy(cfg, params, fast_p, 26)
    finally:
        eng.shutdown()
