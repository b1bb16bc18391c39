"""The `minicpm_sala` family (`ray_tpu/models/minicpm_sala.py`): block-sparse
attention that chooses its pages through a pool of compressed keys, among
lightning linear-attention layers, at tiny widths on the CPU (`dense_len`
64, blocks of 8, the 2 best of the others kept beside one initial block and
a window of 2; float32), with the benchmark's plain reference
(`benchmarks/reference/minicpm_sala.py`) as the judge.  In float32 the
engine's greedy tokens are the reference's argmax at every position, for a
prompt under `dense_len`, one over it and one that crosses it while it
decodes; each planted fault of `benchmarks/tools/minicpm_sala_faults.py`
leaves that path.
"""

import numpy as np
import pytest

from tests.tiny_families import SALA_ENGINE as ENGINE
from tests.tiny_families import minicpm_sala as family
from tests.tiny_families import prompts as _prompts
from tests.tiny_families import serve

# (prompt, new tokens): under dense_len to its end; at it; one that
# crosses it while it decodes; over it; over two row blocks of a prompt
REGIMES = {"under": (40, 20), "at_dense_len": (64, 12),
           "crosses_while_decoding": (56, 24), "one_over": (65, 12),
           "over": (100, 24), "well_over": (130, 24)}


@pytest.fixture(scope="module")
def tiny():
    return family.cfg, family.params


@pytest.fixture(scope="module")
def engine(tiny):
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(*tiny, **ENGINE)
    yield eng
    eng.shutdown()


def test_the_tiny_configuration_is_the_familys(tiny):
    """The benchmark's family builds the same configuration from the tiny
    sizes, and the file's checks hold of them."""
    from benchmarks.families import minicpm_sala as bench

    cfg = bench.program_config(family.SIZES, attention="reference", chunk=8,
                               row_block=32, query_block=16)
    assert cfg == tiny[0]
    assert cfg.kept_blocks == 5 and cfg.gathered_pages == 8
    assert cfg.keys_per_block == 4 and cfg.ckey_row == 2 * 4 * 16


@pytest.mark.parametrize("tokens", [40, 64, 100, 128])
def test_model_is_the_reference(tiny, tokens):
    """The whole forward pass of a prompt, under `dense_len` and over it
    (sparse at every position; 100 and 128 run in row blocks)."""
    import jax.numpy as jnp

    from ray_tpu.models.minicpm_sala import MiniCpmSalaModel

    cfg, params = tiny
    seq = _prompts(tokens, [tokens])[0]
    got = MiniCpmSalaModel(cfg).apply(params, jnp.asarray(seq)[None])[0]
    want = family.reference(params, seq)
    assert 0.5 < want.std() < 3.0
    np.testing.assert_allclose(got, want, atol=3e-5)


def test_a_padded_row_is_the_row_alone(tiny):
    """Right-padding leaks into nothing: logits, lightning state, open
    segments, and the compressed keys whose windows lie in the row."""
    import jax.numpy as jnp

    from ray_tpu.models.minicpm_sala import MiniCpmSalaModel

    cfg, params = tiny
    model = MiniCpmSalaModel(cfg)
    seq = _prompts(3, [75])[0]
    alone = model.apply(params, jnp.asarray(seq)[None], jnp.asarray([74]),
                        method=MiniCpmSalaModel.prefill)
    padded = model.apply(params, jnp.asarray(seq + [0] * 53)[None],
                         jnp.asarray([74]), method=MiniCpmSalaModel.prefill)
    np.testing.assert_allclose(padded[0], alone[0], atol=3e-5)
    for a, b in zip(padded[1]["lightning"], alone[1]["lightning"]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-5)
    for a, b in zip(padded[1]["open"], alone[1]["open"]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-5)
    whole = (75 - cfg.kernel_size) // cfg.kernel_stride + 1   # 36 windows
    for a, b in zip(padded[1]["ckeys"], alone[1]["ckeys"]):
        keys = lambda rows: np.asarray(rows).reshape(  # noqa: E731
            -1, cfg.n_kv_heads, cfg.keys_per_block, cfg.head_dim) \
            .transpose(1, 0, 2, 3).reshape(cfg.n_kv_heads, -1, cfg.head_dim)
        np.testing.assert_allclose(keys(a)[:, :whole], keys(b)[:, :whole],
                                   rtol=1e-5, atol=2e-5)
    assert int(padded[2][0]) == 1       # one prompt past dense_len


@pytest.mark.parametrize("regime", list(REGIMES))
def test_engine_streams_are_the_references(engine, regime):
    """Prefill, then decode through the pages, the compressed-key pool and
    the state fixed per slot: the reference's full forward pass, whose
    queries switch to the kept blocks by length as the program's do."""
    from ray_tpu.models.generate import SamplingParams

    n, new = REGIMES[regime]
    prompt = _prompts(n, [n])[0]
    out = engine.generate(prompt, SamplingParams(max_new_tokens=new))
    gap, repeats = family.reference_gap(prompt, out)
    assert gap.max() == 0.0
    assert repeats < 0.2            # the layers decide, not the embedding


def test_streams_side_by_side_and_what_they_counted(tiny):
    """More prompts than slots, of every regime, admitted mid-flight; the
    step counters: a row past dense_len reads 5 pages a (layer, K/V head)
    table, a row under it its resident ones."""
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = tiny
    eng = LLMEngine(cfg, params, **ENGINE)
    try:
        prompts = _prompts(7, [40, 100, 56, 130, 20])
        outs = serve(eng, prompts, new=24)
        for p, o in zip(prompts, outs):
            assert family.reference_gap(p, o)[0].max() == 0.0
        got = eng.report_metrics()
    finally:
        eng.shutdown()
    assert got["state_slots_reset"] == 5 and got["sparse_prompts"] == 2
    assert got["state_bytes_per_slot"] == \
        2 * (2 * 2 * 16 + 4 * 16 * 16) * 4
    assert 0 < got["sparse_pages_read"] < got["sparse_pages_resident"]
    assert got["sparse_rows"] > 0 and got["compressed_keys_read"] > 0
    # every live row reads at most its resident pages; a sparse one 5
    steps = got["state_slot_steps"]
    tables = 2 * cfg.n_kv_heads
    assert got["sparse_pages_read"] >= \
        got["sparse_rows"] * tables * cfg.kept_blocks
    assert got["sparse_pages_read"] <= steps * tables * cfg.gathered_pages


def test_a_parked_and_resumed_slot_keeps_its_state(tiny):
    """A consumer that does not drain parks its slot (the chunk gives it no
    steps): its open segments, lightning state and compressed keys stand
    still while its neighbour decodes, and the stream it resumes is the
    reference's."""
    from ray_tpu.models.generate import SamplingParams
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = tiny
    eng = LLMEngine(cfg, params, **dict(ENGINE, stream_buffer=4))
    try:
        slow_prompt, fast_prompt = _prompts(11, [70, 30])
        slow = eng.submit(slow_prompt, SamplingParams(max_new_tokens=24))
        fast = eng.generate(fast_prompt, SamplingParams(max_new_tokens=40))
        assert eng.report_metrics()["parked_events"] > 0
        out = slow.tokens()
    finally:
        eng.shutdown()
    assert family.reference_gap(fast_prompt, fast)[0].max() == 0.0
    assert family.reference_gap(slow_prompt, out)[0].max() == 0.0


def test_the_decode_program_holds_a_row_that_is_not_live(tiny):
    """`live` False: what is fixed per slot stays bit for bit, and no
    compressed key is written."""
    import jax.numpy as jnp

    from ray_tpu.serve.llm_families import family_of

    cfg, params = tiny
    fam = family_of(cfg, 160)
    seq = _prompts(5, [78])[0]
    _, fresh, _ = fam.prefill(params, jnp.asarray(seq + [0] * 50)[None],
                              jnp.asarray([77]))
    state = fam.init_state(2, 24, 8)
    table = jnp.arange(1, 21, dtype=jnp.int32)[None].repeat(2, 0)
    state = fam.write_prompt(state, fresh, jnp.asarray([0]), table[:1, :16])
    # (the token at position 77 closes a segment and a window)
    args = (params, jnp.asarray([5, 5]), jnp.asarray([78, 0]), state, table,
            jnp.asarray([78, 0]))
    _, held, _ = fam.decode(*args, jnp.asarray([False, False]))
    for name in ("open", "lightning", "cpools"):
        for a, b in zip(held[name], state[name]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _, moved, counts = fam.decode(*args, jnp.asarray([True, False]))
    assert not np.array_equal(np.asarray(moved["lightning"][0][0]),
                              np.asarray(state["lightning"][0][0]))
    np.testing.assert_array_equal(np.asarray(moved["lightning"][0][1]),
                                  np.asarray(state["lightning"][0][1]))
    # 79 tokens past dense_len: 5 kept of 10 resident pages, 4 tables
    assert counts.tolist() == [20, 40, 1, 4 * 38]


def test_paged_kernel_over_a_gathered_table_is_jnp():
    """The decode kernel as the sparse layers call it, in interpret mode:
    a row of its batch is a (sequence, K/V head) with a table of its own
    pages in ANY order, the partial page last; it writes the step's row
    there and attends over the gathered pages' tokens only."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import paged_decode_attention_batch

    rng = np.random.default_rng(0)
    P, page, D, G, rows = 40, 8, 16, 2, 4
    k_pool = jnp.asarray(rng.normal(size=(P, 1, page, D)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(P, 1, page, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(rows, G, D)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(rows, 1, D)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(rows, 1, D)), jnp.float32)
    # (a row's pages are its own: it writes one of them)
    tables = jnp.asarray(rng.permutation(P)[: rows * 6].reshape(rows, 6),
                         jnp.int32)
    # gathered lengths: whole pages, then 1..8 tokens of the last
    lengths = jnp.asarray([3 * page + 1, 5 * page + 8, 0 * page + 3,
                           2 * page + 5], jnp.int32)
    out, kp, vp = paged_decode_attention_batch(
        q, k_pool, v_pool, tables, lengths, k_new=k_new, v_new=v_new)
    for r in range(rows):
        n = int(lengths[r])
        pages = np.asarray(tables[r][: -(-n // page)])
        at = (n - 1) % page
        np.testing.assert_array_equal(np.asarray(kp[pages[-1], 0, at]),
                                      np.asarray(k_new[r, 0]))
        keys = np.asarray(kp[pages, 0]).reshape(-1, D)[:n]
        vals = np.asarray(vp[pages, 0]).reshape(-1, D)[:n]
        w = jax.nn.softmax(np.asarray(q[r]) @ keys.T / np.sqrt(D), axis=-1)
        np.testing.assert_allclose(out[r], w @ vals, atol=2e-5)


def test_the_shared_scan_with_a_constant_decay_is_the_recurrence():
    """`granite_hybrid.ssd_scan` with a head its own B and C and a step
    size of 1 (lightning attention), in chunks and carried across two
    calls, against the recurrence token by token (`state_step`); a
    position whose step size is 0 leaves the state alone."""
    import jax.numpy as jnp

    from ray_tpu.models.granite_hybrid import ssd_scan, state_step

    rng = np.random.default_rng(1)
    B, S, H, D = 2, 37, 4, 16
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
               for _ in range(3))
    a = -(2.0 ** (-8.0 * jnp.arange(1, H + 1) / H))
    last = jnp.asarray([36, 20])
    dt = jnp.broadcast_to((jnp.arange(S)[None] <= last[:, None])
                          .astype(jnp.float32)[..., None], (B, S, H))
    s = jnp.zeros((B, H, D, D))
    ys = []
    for t in range(S):
        y, s_new = state_step(s, jnp.exp(a), v[:, t], k[:, t], q[:, t])
        live = (t <= last)[:, None, None, None]
        s = jnp.where(live, s_new, s)
        ys.append(y)
    want = jnp.stack(ys, axis=1)
    y1, s1 = ssd_scan(v[:, :16], dt[:, :16], a, k[:, :16], q[:, :16],
                      chunk=8)
    y2, s2 = ssd_scan(v[:, 16:], dt[:, 16:], a, k[:, 16:], q[:, 16:], s1,
                      chunk=8)
    got = jnp.concatenate([y1, y2], axis=1)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(got[1, :21], want[1, :21], rtol=1e-5,
                               atol=2e-5)
    np.testing.assert_allclose(s2, s, rtol=1e-5, atol=2e-5)


def test_the_best_k_by_bisection_are_top_ks():
    """`best_of`: the k highest of a row by bisection on the scores' bits,
    equal scores by lower index (as `lax.top_k` and the reference's rank
    order them), fewer where fewer compete."""
    import jax.numpy as jnp

    from ray_tpu.models.minicpm_sala import best_of

    rng = np.random.default_rng(0)
    for trial in range(12):
        n, k = int(rng.integers(3, 40)), int(rng.integers(1, 8))
        c = rng.random((5, n)).astype(np.float32)
        c[rng.random((5, n)) < 0.3] = -1.0      # these do not compete
        c[:, :2] = c[:, 2:3]                    # ties
        if trial % 3 == 0:
            c = np.round(c, 1)                  # many ties
        got = np.asarray(best_of(jnp.asarray(c), k))
        for r in range(5):
            order = sorted(range(n), key=lambda i: (-c[r, i], i))[:k]
            want = np.zeros(n, bool)
            want[[i for i in order if c[r, i] >= 0]] = True
            np.testing.assert_array_equal(got[r], want)


def _faults():
    from benchmarks.tools.minicpm_sala_faults import FAULTS

    return list(FAULTS)


@pytest.mark.parametrize("fault", [
    "i_dense_where_sparse_is_due", "ii_top_k_one_short",
    "iii_windows_blocks_dropped", "iv_compressed_keys_never_extended",
    "v_decay_of_the_wrong_head", "vi_output_gate_left_out",
    "vii_scale_depth_over_the_root_of_the_layers_held"])
def test_a_planted_fault_is_seen(tiny, fault):
    """Each of the tool's faults, at tiny widths: the engine's streams
    leave the reference's greedy path (the sound engine's are held to 0.0
    above)."""
    from benchmarks.tools import minicpm_sala_faults as tool

    assert fault in _faults() and len(_faults()) == 7
    cfg, params = tiny
    prompts = _prompts(13, [40, 100, 60, 120])
    samples, _ = tool.serve(cfg, params, ENGINE, prompts, 24, fault)
    gaps = [family.reference_gap(s["prompt"], s["output"])[0].max()
            for s in samples]
    assert max(gaps) > 1e-3, gaps


def test_the_selection_tie_pass_keeps_the_other_block(tiny):
    """The reference's last level: with a tie as wide as any score, every
    selection with a block left over keeps the first dropped in place of
    the last kept, and the logits move; with no tie at all it is the
    served pass."""
    from benchmarks.reference import minicpm_sala as ref

    _, params = tiny
    seq = _prompts(17, [120])[0]
    served = family.reference(params, seq, rounded=ref.TIE_PASS - 1)
    old = ref.SELECTION_TIE
    try:
        ref.SELECTION_TIE = 0.0
        ref.layer.clear_cache()
        tie = family.reference(params, seq, rounded=ref.TIE_PASS)
        ref.SELECTION_TIE = 10.0
        ref.layer.clear_cache()
        other = family.reference(params, seq, rounded=ref.TIE_PASS)
    finally:
        ref.SELECTION_TIE = old
        ref.layer.clear_cache()
    np.testing.assert_array_equal(tie, served)
    assert np.abs(other - served).max() > 1e-3
    # a query with no block left over keeps what it kept
    np.testing.assert_allclose(other[:40], served[:40], atol=1e-6)


def test_params_are_counted(tiny):
    import jax

    from ray_tpu.models.minicpm_sala import (MINICPM_SALA_L8, count_params)

    cfg, params = tiny
    assert count_params(cfg)["total"] == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    # ISSUE 49's arithmetic: 2 x 253.8 M + 6 x 285.2 M + 601.7 M
    assert count_params(MINICPM_SALA_L8)["total"] == 2_820_545_280


@pytest.mark.parametrize("stds", [0.05, (0.01, 0.05)])
def test_a_sparse_layers_w_o_takes_its_own_deviation(stds):
    """`init_params`: one deviation for every sparse layer's W_o, or one
    for each in order (the benchmark's first sparse layer writes less than
    its last: `benchmarks/families/minicpm_sala.WEIGHTS`)."""
    import jax

    from ray_tpu.models.minicpm_sala import SPARSE, init_params

    cfg = family.cfg
    p = init_params(cfg, jax.random.PRNGKey(0), sparse_out_std=stds,
                    lightning_out_std=0.2)["params"]
    want = stds if isinstance(stds, tuple) else (stds, stds)
    for i, std in zip(cfg.layers_of(SPARSE), want):
        got = float(np.std(np.asarray(p[f"layers_{i}"]["o_proj"])))
        assert abs(got / std - 1) < 0.1
