"""Disaggregated serving tests: prefix cache semantics, prefill→decode
KV handoff correctness against the one-shot Generator reference, the
two-pool e2e with device-plane route proof, and per-pool autoscaling on
replica-reported metrics (reference model: Serve LLM apps over
vLLM-style disaggregated prefill/decode engine pools)."""

import os
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.models.generate import SamplingParams
from ray_tpu.serve.llm import LLMEngine, _Prefilled
from ray_tpu.serve.llm_disagg import PrefillEngine, PrefixCache
from ray_tpu.test_utils import wait_for_condition
from tests.tiny_families import dense


@pytest.fixture(scope="module")
def tiny_model():
    return dense.cfg, dense.params


@pytest.fixture
def collective_env():
    """Force the collective route on the CPU backend — set BEFORE ray
    init so spawned replica workers inherit it."""
    os.environ["RAY_TPU_DEVICE_COLLECTIVE"] = "1"
    yield
    os.environ.pop("RAY_TPU_DEVICE_COLLECTIVE", None)


def _reference_greedy(cfg, params, prompt, n_new):
    return dense.greedy(prompt, n_new)


# ---------------------------------------------------------------------------
# Prefix cache (pure unit)
# ---------------------------------------------------------------------------


def test_prefix_cache_hit_miss_eviction():
    cache = PrefixCache(max_entries=2)
    kv = [(np.zeros((2, 3, 4)), np.zeros((2, 3, 4)))]
    logits = np.zeros(8)

    hit, _ = cache.lookup([1, 2, 3])
    assert hit == "miss"
    cache.insert([1, 2, 3], kv, logits)
    hit, entry = cache.lookup([1, 2, 3])
    assert hit == "full" and entry["prefix_len"] == 3
    # A cached prompt that is a strict prefix of the query → partial.
    hit, entry = cache.lookup([1, 2, 3, 9, 9])
    assert hit == "partial" and entry["prefix_len"] == 3
    # Longest strict prefix wins.
    cache.insert([1, 2, 3, 9], kv, logits)
    hit, entry = cache.lookup([1, 2, 3, 9, 9])
    assert hit == "partial" and entry["prefix_len"] == 4
    # Sharing a prefix is not enough — the CACHED prompt must be the
    # prefix ([1,2,3,9] is not a prefix of [1,2,4]).
    hit, _ = cache.lookup([1, 2, 4])
    assert hit == "miss"
    # Bounded: inserting a third entry evicts the LRU one.
    cache.insert([7, 7, 7], kv, logits)
    assert cache.stats()["entries"] == 2
    assert cache.stats()["evictions"] == 1
    stats = cache.stats()
    assert stats["hits"] == 3 and stats["misses"] == 2
    assert 0 < stats["hit_rate"] < 1


def test_prefix_cache_full_hit_skips_prefill(tiny_model):
    """A repeated prompt reuses cached KV + last logits: the compiled
    prefill program is NOT invoked, and the handed-off stream still
    matches the reference exactly."""
    cfg, params = tiny_model
    pe = PrefillEngine(cfg, params, max_len=96)
    cache = PrefixCache(8)
    eng = LLMEngine(cfg, params, max_batch=2, max_len=96, page_size=16)
    calls = {"one": 0, "suffix": 0}
    real_one, real_suffix = pe._prefill_one, pe._prefill_suffix

    def count_one(*a):
        calls["one"] += 1
        return real_one(*a)

    def count_suffix(*a):
        calls["suffix"] += 1
        return real_suffix(*a)

    pe._prefill_one, pe._prefill_suffix = count_one, count_suffix
    try:
        prompt = [1, 5, 9, 2, 7]
        sp = SamplingParams(max_new_tokens=12)
        expected = _reference_greedy(cfg, params, prompt, 12)

        def run(expect_hit):
            out = pe.prefill(np.asarray(prompt), sp, cache)
            assert out["prefix_hit"] == expect_hit
            pack = _Prefilled(out["kv"], out["first_token"],
                              out["prompt_len"], out["kv_len"], 0, [],
                              emit_first=True)
            assert eng.submit_prefilled(pack, sp).tokens() == expected

        run("miss")
        assert calls == {"one": 1, "suffix": 0}
        run("full")  # hit: no prefill program ran
        assert calls == {"one": 1, "suffix": 0}
        # Extension of a cached prompt: only the SUFFIX program runs.
        ext = prompt + [3, 8]
        out = pe.prefill(np.asarray(ext), sp, cache)
        assert out["prefix_hit"] == "partial"
        assert calls == {"one": 1, "suffix": 1}
        pack = _Prefilled(out["kv"], out["first_token"], out["prompt_len"],
                          out["kv_len"], 0, [], emit_first=True)
        assert eng.submit_prefilled(pack, sp).tokens() == \
            _reference_greedy(cfg, params, ext, 12)
    finally:
        eng.shutdown()


def test_prefilled_handoff_into_paged_engine(tiny_model):
    """The prefill-pool KV lands in a paged decode engine's pools via
    submit_prefilled and decodes to the exact reference output."""
    cfg, params = tiny_model
    pe = PrefillEngine(cfg, params, max_len=96)
    eng = LLMEngine(cfg, params, max_batch=2, max_len=96, page_size=16,
                    kv_pool_tokens=96 * 4)
    try:
        prompt = [4, 4, 6, 2, 9, 1, 3]
        sp = SamplingParams(max_new_tokens=10)
        out = pe.prefill(np.asarray(prompt), sp, None)
        pack = _Prefilled(out["kv"], out["first_token"], out["prompt_len"],
                          out["kv_len"], 0, [], emit_first=True)
        assert eng.submit_prefilled(pack, sp).tokens() == \
            _reference_greedy(cfg, params, prompt, 10)
    finally:
        eng.shutdown()


@pytest.mark.parametrize("prompt_len", [7, 16, 40, 70],
                         ids=["in-a-page", "a-page-full", "mid", "max_len"])
def test_prefilled_kv_equals_what_the_engine_prefills_itself(tiny_model,
                                                             prompt_len):
    """The prefill pool still attends over a dense cache (it holds earlier
    keys when a prefix hits); the decode engine's own prefill is the
    prompt over itself. Both put the same K/V into pages: a stream handed
    over through `submit_prefilled` and the same prompt submitted plainly
    give the same tokens, the Generator's."""
    cfg, params = tiny_model
    pe = PrefillEngine(cfg, params, max_len=96)
    eng = LLMEngine(cfg, params, max_batch=2, max_len=96, page_size=16)
    try:
        prompt = [(i * 13 + 2) % 120 + 1 for i in range(prompt_len)]
        sp = SamplingParams(max_new_tokens=10)
        out = pe.prefill(np.asarray(prompt), sp, None)
        assert all(k.shape == (cfg.n_kv_heads, prompt_len, cfg.head_dim)
                   for k, _v in out["kv"])
        pack = _Prefilled(out["kv"], out["first_token"], out["prompt_len"],
                          out["kv_len"], 0, [], emit_first=True)
        want = _reference_greedy(cfg, params, prompt, 10)
        assert eng.submit_prefilled(pack, sp).tokens() == want
        assert eng.generate(prompt, sp) == want
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# Two-pool e2e
# ---------------------------------------------------------------------------


@pytest.mark.smoke
def test_disagg_two_pools_collective_route(tiny_model, collective_env,
                                           ray_start_regular):
    """Acceptance scenario: ≥2 prefill + ≥2 decode replicas complete a
    concurrent-stream workload; the decode-side route counters prove the
    KV handoff used the device plane (collective) and NEVER the
    consumer-side host path; prefix-cache hit rate > 0 on repeated
    prompts."""
    from ray_tpu import serve
    from ray_tpu.serve import llm_disagg

    cfg, params = tiny_model
    h = llm_disagg.deploy_disagg(
        cfg, params, prefill_replicas=2, decode_replicas=2,
        max_batch=2, max_len=96, page_size=16,
        prefill_actor_options={"num_cpus": 0},
        decode_actor_options={"num_cpus": 0})
    try:
        prompts = [[1, 5, 9, 2, 7], [4, 4, 6], [1, 5, 9, 2, 7],
                   [1, 5, 9, 2, 7, 3, 8]]  # repeat + extension → cache hits
        expected = [_reference_greedy(cfg, params, p, 10) for p in prompts]
        results = [None] * len(prompts)

        def consume(i):
            results[i] = h.generate({"prompt_tokens": prompts[i],
                                     "max_new_tokens": 10})

        threads = [threading.Thread(target=consume, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert results == expected
        # Which prefill replica the four concurrent prompts reach, and in
        # what order, is the router's and the scheduler's: the repeats can
        # each meet a replica that has not seen their prefix yet (one run
        # of the whole suite did).  Three more of one prompt, one after
        # the other, over two replicas: one of them is a hit whatever the
        # routing.
        for _ in range(3):
            assert h.generate({"prompt_tokens": prompts[0],
                               "max_new_tokens": 10}) == expected[0]
        pm = h.pool_metrics()
        hits = sum(m.get("prefix_cache_hits", 0) for m in pm["prefill"])
        assert hits > 0, pm["prefill"]
        collective = sum(m["plane_counters"].get("collective", 0)
                         for m in pm["decode"])
        host = sum(m["plane_counters"].get("host_fallback", 0)
                   for m in pm["decode"])
        assert collective > 0, pm["decode"]
        assert host == 0, pm["decode"]
        assert h.stats["completed"] == len(prompts) + 3
        assert h.stats["resumes"] == 0
    finally:
        serve.shutdown()


@pytest.mark.smoke
def test_disagg_per_pool_autoscaling(tiny_model, ray_start_regular):
    """Each pool scales on ITS OWN replica-reported signal: six callers
    streaming back to back push prefill TTFT and decode tokens_in_flight
    over their targets, the controller grows both pools independently,
    and once the load stops the decode pool (short downscale delay)
    returns to min while prefill (long delay) stays scaled out."""
    from ray_tpu import serve
    from ray_tpu.serve import llm_disagg

    cfg, params = tiny_model
    h = llm_disagg.deploy_disagg(
        cfg, params, prefill_replicas=1, decode_replicas=1,
        max_batch=4, max_len=96, page_size=16,
        # TTFT includes queue wait + first-touch compile, and the
        # replica's TTFT deque keeps it observable after the burst —
        # queue_depth on a tiny CPU model drains between controller
        # ticks and would flake.
        prefill_autoscaling={"min_replicas": 1, "max_replicas": 2,
                             "metric": "ttft_p99_ms", "target_value": 25.0,
                             "look_back_period_s": 30.0,
                             "upscale_delay_s": 0.0,
                             "downscale_delay_s": 600.0},
        decode_autoscaling={"min_replicas": 1, "max_replicas": 2,
                            "metric": "tokens_in_flight",
                            "target_value": 16.0,
                            "look_back_period_s": 4.0,
                            "upscale_delay_s": 0.0,
                            "downscale_delay_s": 6.0},
        prefill_actor_options={"num_cpus": 0},
        decode_actor_options={"num_cpus": 0})
    try:
        prompt = [1, 5, 9, 2, 7]
        expected = _reference_greedy(cfg, params, prompt, 48)
        outs = [[] for _ in range(6)]
        grown = threading.Event()

        def consume(i):
            # Stream after stream until both pools have grown: six callers
            # on four slots keep every controller tick (2 s) under load.
            # One burst of six streams was over in 4.5 s, two ticks, and
            # as often as not the decode pool's signal never saw it.
            while not grown.is_set():
                outs[i].append(list(h.stream({"prompt_tokens": prompt,
                                              "max_new_tokens": 48})))

        threads = [threading.Thread(target=consume, args=(i,))
                   for i in range(len(outs))]
        for t in threads:
            t.start()
        wait_for_condition(
            lambda: len(h._prefill._get_replicas()) == 2
            and len(h._decode._get_replicas()) == 2, timeout=90)
        grown.set()
        for t in threads:
            t.join(timeout=120)
        assert all(o == expected for per in outs for o in per)
        assert all(outs)
        # Load gone: decode's signal decays and it scales back to min.
        wait_for_condition(
            lambda: len(h._decode._get_replicas()) == 1, timeout=90)
        # Prefill (600s downscale delay) must still be scaled out —
        # proof the two pools act on independent signals.
        assert len(h._prefill._get_replicas()) == 2
    finally:
        serve.shutdown()
