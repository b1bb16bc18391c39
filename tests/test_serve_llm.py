"""Continuous-batching LLM engine tests (reference: serve LLM apps run on
external engines; here the engine is native — correctness is checked
against the one-shot Generator, which is the spec for greedy decoding)."""

import time

import pytest

from ray_tpu.models.generate import SamplingParams
from ray_tpu.serve.llm import LLMEngine, _Prefilled
from tests.tiny_families import dense


@pytest.fixture(scope="module")
def tiny_model():
    return dense.cfg, dense.params


@pytest.fixture(scope="module")
def engine(tiny_model):
    """One engine for the tests that only submit to it and read what comes
    back: each leaves it idle."""
    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, max_batch=3, max_len=96, page_size=16)
    yield eng
    eng.shutdown()


def _reference_greedy(cfg, params, prompt, n_new):
    return dense.greedy(prompt, n_new)


@pytest.fixture(scope="module", params=[0, 64],
                ids=["default-pool", "small-pool"])
def pooled_engine(request, tiny_model):
    """The engine with room for every slot's longest stream (the default
    pool), and with fewer tokens than three requests need at once, so
    that admission waits for pages."""
    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, max_batch=3, max_len=96, page_size=16,
                    kv_pool_tokens=request.param)
    yield eng
    eng.shutdown()


def test_engine_matches_generator_greedy(tiny_model, pooled_engine):
    cfg, params = tiny_model
    prompt = [1, 5, 9, 2, 7]
    expected = _reference_greedy(cfg, params, prompt, 12)
    got = pooled_engine.generate(prompt, SamplingParams(max_new_tokens=12))
    assert got == expected


# What the engine of PR 36 (prefill over a dense cache of `max_len`, masked
# softmax in float32) gave for `_PARENT_PROMPTS` on this model, 16 tokens
# each, greedy, three streams at once.
_PARENT_PROMPTS = [[(i * 5 + 3 * n) % 120 + 1 for i in range(n)]
                   for n in (5, 37, 70)]
_PARENT_TOKENS = [
    [52, 113, 113, 39, 39, 39, 39, 39, 39, 39, 39, 39, 39, 39, 39, 39],
    [113, 113, 119, 37, 29, 6, 102, 37, 15, 121, 113, 10, 124, 84, 21, 90],
    [65, 32, 117, 110, 65, 86, 61, 98, 86, 27, 65, 86, 106, 23, 106, 34]]


@pytest.mark.parametrize("which", [0, 1, 2],
                         ids=["one-page", "three-pages", "max_len-bucket"])
def test_greedy_tokens_are_what_the_dense_cache_prefill_gave(tiny_model,
                                                             engine, which):
    """The prefill is the prompt over itself through the flash kernel
    now; the tokens are those the engine gave before, and the
    Generator's (which still prefills over a dense cache)."""
    cfg, params = tiny_model
    prompt = _PARENT_PROMPTS[which]
    got = engine.generate(prompt, SamplingParams(max_new_tokens=16))
    assert got == _PARENT_TOKENS[which]
    assert got == _reference_greedy(cfg, params, prompt, 16)


def test_engine_concurrent_requests_interleave(tiny_model, pooled_engine):
    cfg, params = tiny_model
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8, 9, 10], [11, 12]]
    expected = [_reference_greedy(cfg, params, p, 10) for p in prompts]
    # Submit all three concurrently: slots decode in one batched program
    # (two pages a request: the small pool holds two of them at a time).
    handles = [pooled_engine.submit(p, SamplingParams(max_new_tokens=10))
               for p in prompts]
    results = [h.tokens() for h in handles]
    assert results == expected
    alloc = pooled_engine._alloc
    assert alloc.free_pages == alloc.num_pages - 1  # all but the dummy


def test_engine_admission_mid_flight(tiny_model, engine):
    """A request submitted while another is decoding joins the batch and
    both match the sequential reference."""
    cfg, params = tiny_model
    h1 = engine.submit([1, 2, 3, 4], SamplingParams(max_new_tokens=30))
    it1 = iter(h1)
    first = [next(it1) for _ in range(3)]  # h1 is definitely mid-decode
    h2 = engine.submit([9, 8, 7], SamplingParams(max_new_tokens=10))
    rest = list(it1)
    out2 = h2.tokens()
    assert first + rest == _reference_greedy(cfg, params, [1, 2, 3, 4], 30)
    assert out2 == _reference_greedy(cfg, params, [9, 8, 7], 10)


def test_engine_eos_and_overflow(tiny_model, engine):
    cfg, params = tiny_model
    ref = _reference_greedy(cfg, params, [3, 3, 3], 20)
    eos = ref[5]  # pick a token we know appears in the reference output
    got = engine.generate([3, 3, 3],
                          SamplingParams(max_new_tokens=20, eos_token=eos))
    # Stops at (and includes) the FIRST occurrence of the eos token —
    # which may precede step 5 (token values depend on the tiny random
    # model's numerics, which shift across jax versions).
    assert got == ref[:ref.index(eos) + 1]
    with pytest.raises(ValueError, match="exceeds engine max_len"):
        engine.submit(list(range(90)), SamplingParams(max_new_tokens=20))


def test_engine_topk1_equals_greedy(tiny_model, engine):
    """top_k=1 collapses sampling to argmax regardless of temperature —
    checks the per-slot top-k mask is actually applied."""
    cfg, params = tiny_model
    expected = _reference_greedy(cfg, params, [2, 4, 6], 8)
    got = engine.generate([2, 4, 6], SamplingParams(
        max_new_tokens=8, temperature=1.5, top_k=1))
    assert got == expected


def test_llm_server_streams_through_serve(tiny_model, ray_start_regular):
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMServer

    cfg, params = tiny_model
    expected = _reference_greedy(cfg, params, [1, 2, 3], 8)

    @serve.deployment
    class TinyLLM(LLMServer):
        def __init__(self):
            super().__init__(cfg, params, max_batch=2, max_len=64,
                             page_size=16)

    serve.run(TinyLLM.bind())
    try:
        handle = serve.get_deployment_handle("TinyLLM")
        toks = list(handle.options(stream=True).remote(
            {"prompt_tokens": [1, 2, 3], "max_new_tokens": 8}))
        assert toks == expected
    finally:
        serve.shutdown()


def test_a_long_prompt_admitted_mid_decode_keeps_every_stream_exact(
        tiny_model):
    """A 60-token prompt (four pages, the 64-token bucket) is prefilled
    whole between two chunks of two streams that are mid-decode: its
    pages and first token land beside theirs, and all three streams are
    the Generator's."""
    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, max_batch=3, max_len=96, page_size=16,
                    decode_chunk=4, stream_buffer=8)
    try:
        prompts = [[5, 6], [1, 5, 9, 2, 7, 3, 8]]
        long_prompt = [(i * 11 + 2) % 120 for i in range(60)]
        hs = [eng.submit(p, SamplingParams(max_new_tokens=30))
              for p in prompts]
        its = [iter(h) for h in hs]
        outs = [[next(it) for _ in range(3)] for it in its]
        # Both are mid-decode whatever the machine's speed: a stream runs
        # at most its 8-token buffer ahead of what was read.
        assert eng.quiesce_for_drain()
        assert eng.num_active() == 2
        h_long = eng.submit(long_prompt, SamplingParams(max_new_tokens=8))
        eng.resume()
        out_long = h_long.tokens()
        for out, it in zip(outs, its):
            out.extend(it)
        assert out_long == _reference_greedy(cfg, params, long_prompt, 8)
        assert outs == [_reference_greedy(cfg, params, p, 30)
                        for p in prompts]
    finally:
        eng.shutdown()


def test_engine_is_paged_by_default_and_refuses_page_size_zero(tiny_model):
    """No size given: pages of 64 tokens and a pool with room for every
    slot's longest stream, `max_batch * (max_len + decode_chunk)` tokens,
    plus the dummy page. A page size that is no size is refused in words."""
    cfg, params = tiny_model
    eng = LLMEngine(cfg, params)
    try:
        assert (eng.max_batch, eng.max_len, eng.decode_chunk,
                eng.page_size) == (4, 1024, 8, 64)
        assert eng._alloc.num_pages == -(-4 * (1024 + 8) // 64) + 1
        assert eng.queue_depth() == 0
    finally:
        eng.shutdown()
    with pytest.raises(ValueError, match="page_size=0 must be positive"):
        LLMEngine(cfg, params, page_size=0)
    with pytest.raises(ValueError, match="multiple of page_size=64"):
        LLMEngine(cfg, params, max_len=96)


def test_stream_backpressure_parks_and_resumes(tiny_model):
    """A slow consumer fills its bounded stream buffer: the slot PARKS
    (decode pauses for that stream instead of growing an unbounded
    queue) and resumes as the consumer drains — output still matches the
    reference exactly."""
    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, max_batch=2, max_len=96, decode_chunk=4,
                    page_size=16, stream_buffer=4)
    try:
        prompts = [[1, 5, 9, 2, 7], [4, 4, 6]]
        expected = [_reference_greedy(cfg, params, p, 24) for p in prompts]
        hs = [eng.submit(p, SamplingParams(max_new_tokens=24))
              for p in prompts]
        outs = [[], []]
        its = [iter(h) for h in hs]
        for i, it in enumerate(its):
            for _ in range(3):
                outs[i].append(next(it))
        time.sleep(1.0)  # decode runs ahead, fills both buffers, parks
        assert all(h.backlog_full() for h in hs)
        for i, it in enumerate(its):
            for t in it:
                outs[i].append(t)
                time.sleep(0.01)
        assert outs == expected
        assert eng.report_metrics()["parked_events"] > 0
    finally:
        eng.shutdown()


@pytest.mark.smoke
def test_decode_drain_midstream_zero_loss(tiny_model, ray_start_cluster_head):
    """Preempting a decode node mid-stream loses NOTHING: the drain
    pipeline evacuates each in-flight stream's KV + cursor to the
    router, which replays the tokens the consumer never saw and resumes
    decoding on a surviving replica — both streams match the reference
    exactly (zero dropped, zero duplicated) and ≥1 KV evacuation
    actually rode the device-object drain path."""
    from ray_tpu import serve
    from ray_tpu._private import device_objects
    from ray_tpu.serve import llm_disagg
    from ray_tpu.test_utils import NodePreempter

    cluster = ray_start_cluster_head
    cfg, params = tiny_model
    nodes = [cluster.add_node(num_cpus=2, resources={"decode": 1})
             for _ in range(2)]
    cluster.wait_for_nodes()
    before = dict(device_objects.counters())
    h = llm_disagg.deploy_disagg(
        cfg, params, prefill_replicas=1, decode_replicas=2,
        max_batch=2, max_len=96, page_size=16, stream_buffer=4,
        prefill_actor_options={"num_cpus": 0},
        decode_actor_options={"num_cpus": 0, "resources": {"decode": 1}})
    try:
        prompts = [[1, 5, 9, 2, 7], [4, 4, 6]]
        expected = [_reference_greedy(cfg, params, p, 24) for p in prompts]
        gens = [h.stream({"prompt_tokens": p, "max_new_tokens": 24})
                for p in prompts]
        got = [[], []]
        for i, g in enumerate(gens):
            for _ in range(3):
                got[i].append(next(g))
        time.sleep(1.5)  # decode fills the tiny stream buffers and parks
        # Preempt a node that actually hosts an active stream — the
        # power-of-two picker may have put both streams on one replica.
        target = None
        for m in h.pool_metrics()["decode"]:
            if m.get("active_streams", 0) > 0:
                target = next(n for n in nodes
                              if n.node_id == m["node_id"])
                break
        assert target is not None, "no decode replica reported a stream"
        res = NodePreempter(cluster, deadline_s=10, reason="preemption",
                            respawn=True).preempt(target)
        assert res.get("state") == "DRAINED"
        for i, g in enumerate(gens):
            got[i].extend(g)
        assert got == expected  # zero dropped, zero duplicated
        assert h.stats["evac_resumes"] >= 1
        evac_in = device_objects.counters()["evacuated_in"] - \
            before.get("evacuated_in", 0)
        assert evac_in > 0  # the stream KV rode the evacuation path
    finally:
        serve.shutdown()


def test_snapshot_of_a_paged_engine_resumes_on_another(tiny_model):
    """What a drained replica does, without the cluster: two streams
    mid-decode are snapshotted (their pages gathered by table row back
    into a per-layer prefix) and handed to an engine with another page
    size and another pool; what was generated before plus what the
    second engine generates is the Generator's stream."""
    cfg, params = tiny_model
    src = LLMEngine(cfg, params, max_batch=2, max_len=96, page_size=16,
                    decode_chunk=4, stream_buffer=4)
    dst = LLMEngine(cfg, params, max_batch=2, max_len=96, page_size=32,
                    decode_chunk=4, kv_pool_tokens=192)
    try:
        # One stream on one page, the other over three.
        prompts = {"short": [1, 5, 9, 2, 7],
                   "long": [(i * 7 + 3) % 120 for i in range(37)]}
        sp = SamplingParams(max_new_tokens=30)
        hs = {tag: src.submit(p, sp, tag=tag) for tag, p in prompts.items()}
        for h in hs.values():
            it = iter(h)
            for _ in range(3):
                next(it)
        assert src.quiesce_for_drain()
        snap = src.snapshot_active_streams()
        assert set(snap) == set(prompts)
        for tag, s in snap.items():
            # mid-decode: at most the 4-token buffer ahead of the reader
            assert 3 <= s["generated"] < 30
            assert s["lens"] == len(prompts[tag]) + s["generated"] - 1
            assert all(k.shape == (cfg.n_kv_heads, s["lens"], cfg.head_dim)
                       for k, _v in s["kv"])
            pack = _Prefilled(s["kv"], s["token"], s["prompt_len"],
                              s["lens"], s["generated"], s["history"],
                              emit_first=False)
            rest = dst.submit_prefilled(
                pack, SamplingParams(**s["sampling"])).tokens()
            assert s["history"] + rest == _reference_greedy(
                cfg, params, prompts[tag], 30)
    finally:
        src.shutdown()
        dst.shutdown()
