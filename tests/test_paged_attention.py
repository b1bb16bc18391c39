"""Paged decode attention kernel + page allocator (vLLM block-table idea,
TPU pallas scalar-prefetch kernel; reference serves LLMs through
vLLM-style engines whose core mechanism this is)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops.paged_attention import (  # noqa: E402
    PageAllocator, paged_decode_attention_batch)


def _ref_attention(q, keys, values, groups):
    """Dense single-query attention reference (numpy)."""
    H, D = q.shape
    Hkv = keys.shape[1]
    out = np.zeros((H, D), np.float32)
    for h in range(H):
        kvh = h // groups
        scores = (keys[:, kvh, :] @ q[h]) / np.sqrt(D)
        p = np.exp(scores - scores.max())
        p /= p.sum()
        out[h] = p @ values[:, kvh, :]
    return out


def test_page_allocator_lifecycle():
    alloc = PageAllocator(num_pages=8, page_size=16)
    assert alloc.free_pages == 8
    a = alloc.allocate("a", 40)   # 3 pages
    assert len(a) == 3 and alloc.free_pages == 5
    a2 = alloc.allocate("a", 70)  # grow to 5 pages
    assert len(a2) == 5 and a2[:3] == a and alloc.free_pages == 3
    t = alloc.table("a", 8)
    assert list(t[:5]) == a2 and t.shape == (8,)
    with pytest.raises(MemoryError):
        alloc.allocate("b", 16 * 4)  # only 3 free
    alloc.free("a")
    assert alloc.free_pages == 8
    b = alloc.allocate("b", 16 * 4)
    assert len(b) == 4


def _pool_and_sequences(rng, lengths, tables, Hkv, D, page, pool_pages):
    """A random pool, and each sequence as its table row reads it: the
    first `length` tokens of the row's pages, (L, Hkv, D)."""
    k_pool = rng.standard_normal((pool_pages, Hkv, page, D)).astype(np.float32)
    v_pool = rng.standard_normal((pool_pages, Hkv, page, D)).astype(np.float32)
    seqs = []
    for L, row in zip(lengths, tables):
        live = row[: -(-L // page)]
        seqs.append(tuple(
            pool[live].transpose(0, 2, 1, 3).reshape(-1, Hkv, D)[:L]
            for pool in (k_pool, v_pool)))
    return k_pool, v_pool, seqs


# What the kernel branches on: (lengths, table width, pages a block may
# hold, Hkv, how each sequence's pages are picked from the pool).
_BATCH_CASES = {
    # 1 token, exactly a page, a page and one, a full table, in one batch
    "ragged": ([1, 8, 9, 40], 5, 2, 4, "shuffled"),
    # a slot of length 0 first, in the middle and last
    "empty_slots": ([0, 17, 0, 40, 0], 5, 2, 4, "shuffled"),
    # 3 and 5 live pages in blocks of 2: the last block is part empty
    "pages_not_multiple_of_block": ([3, 17, 40], 5, 2, 4, "shuffled"),
    # one block holds the whole table (what the VMEM budget gives here)
    "whole_table_one_block": ([3, 17, 40], 5, None, 4, "shuffled"),
    # a block of one page: every page its own block, the slots alternate
    "one_page_blocks": ([3, 17, 40], 5, 1, 4, "shuffled"),
    # page indices that fall, and pages that several sequences share (a
    # common prefix): a row is a list of indices, not a range
    "descending_and_shared_pages": ([24, 33, 40], 5, 2, 4, "shared"),
    "hkv8": ([3, 17, 40], 5, 2, 8, "shuffled"),
    "hkv2": ([3, 17, 40], 5, 2, 2, "shuffled"),
    # one sequence alone: a token, part of a page, two pages exactly, more
    "alone_1": ([1], 5, 2, 4, "shuffled"),
    "alone_7": ([7], 5, 2, 4, "shuffled"),
    "alone_16": ([16], 5, 2, 4, "shuffled"),
    "alone_37": ([37], 5, 2, 4, "shuffled"),
    # every head its own KV head (no groups), tables that rise
    "no_groups_rising_tables": ([5, 17, 24], 3, 2, 8, "rising"),
}


def _batch_case(case, monkeypatch):
    """The inputs of a case (a tuple as in `_BATCH_CASES`): (q, k_pool,
    v_pool, tables, lengths) as numpy, the sequences as their tables read
    them, and H."""
    from ray_tpu.ops import paged_attention

    lengths, NP, pages_per_block, Hkv, picking = case
    H, D, page, pool_pages = 8, 32, 8, 32
    B = len(lengths)
    rng = np.random.default_rng(1)
    if picking == "shared":
        tables = np.array([[20, 11, 5, 25 + b, 28 - b] for b in range(B)],
                          np.int32)
    elif picking == "rising":
        tables = np.arange(B * NP, dtype=np.int32).reshape(B, NP)
    else:
        # past the live pages: one valid index, as the engine's dummy page
        free = list(rng.permutation(pool_pages))
        tables = np.full((B, NP), free.pop(), np.int32)
        for b, L in enumerate(lengths):
            for i in range(min(-(-L // page), NP)):
                tables[b, i] = free.pop()
    k_pool, v_pool, seqs = _pool_and_sequences(
        rng, lengths, tables, Hkv, D, page, pool_pages)
    q = rng.standard_normal((B, H, D)).astype(np.float32)
    page_bytes = Hkv * page * D * 4
    if pages_per_block is not None:
        monkeypatch.setattr(paged_attention, "_KV_VMEM_BYTES",
                            4 * page_bytes * pages_per_block)
    assert paged_attention._pages_per_block(page_bytes, NP) \
        == (pages_per_block or NP)
    return (q, k_pool, v_pool, tables, np.asarray(lengths, np.int32)), seqs, H


@pytest.mark.parametrize("case", list(_BATCH_CASES))
def test_paged_batch_kernel_matches_dense(case, monkeypatch):
    """The kernel (one grid step a sequence, a loop over its live pages
    inside) against the dense reference: the exact shape the paged LLM
    engine uses."""
    args, seqs, H = _batch_case(_BATCH_CASES[case], monkeypatch)
    q, lengths, Hkv = args[0], args[4], args[1].shape[1]
    out = np.asarray(paged_decode_attention_batch(*map(jnp.asarray, args)))
    assert np.isfinite(out).all()
    for b, L in enumerate(lengths):
        if L == 0:
            continue        # an empty slot's row is ignored by the engine
        ref = _ref_attention(q[b], seqs[b][0], seqs[b][1],
                             groups=H // Hkv)
        np.testing.assert_allclose(out[b], ref, rtol=2e-4, atol=2e-4)


def _scattered(pool, tables, lengths, rows):
    """`pool` with `rows[b]` at sequence b's position `lengths[b] - 1`, as
    the one-token scatter the engine used to make outside the kernel."""
    page = pool.shape[2]
    at = np.maximum(lengths - 1, 0)
    pages = tables[np.arange(len(lengths)), at // page]
    return jnp.asarray(pool).at[pages, :, at % page].set(
        jnp.asarray(rows).astype(pool.dtype))


def _rows(seed, args):
    """Random K and V rows for the current tokens of `args`' sequences."""
    _q, k_pool, _v, _tables, lengths = args
    return np.random.default_rng(seed).standard_normal(
        (2, len(lengths), k_pool.shape[1], k_pool.shape[3])).astype(
        np.float32)


def _write(args, k_new, v_new):
    """The kernel's (out, k_pool, v_pool) with the rows handed in."""
    return paged_decode_attention_batch(
        *map(jnp.asarray, args), k_new=jnp.asarray(k_new),
        v_new=jnp.asarray(v_new))


def _written(args, k_new, v_new):
    """`_write`'s result beside what the read-only kernel gives on pools
    scattered beforehand."""
    q, k_pool, v_pool, tables, lengths = args
    k_ref = _scattered(k_pool, tables, lengths, k_new)
    v_ref = _scattered(v_pool, tables, lengths, v_new)
    ref = paged_decode_attention_batch(
        jnp.asarray(q), k_ref, v_ref, jnp.asarray(tables),
        jnp.asarray(lengths))
    return _write(args, k_new, v_new), (ref, k_ref, v_ref)


@pytest.mark.parametrize("case", [c for c, v in _BATCH_CASES.items()
                                  if v[4] != "shared" and 0 not in v[0]])
def test_paged_batch_kernel_writes_the_new_token(case, monkeypatch):
    """Handed the current tokens' rows, the kernel stores them where the
    scatter did, bit for bit and nowhere else, and attends over them.
    (Pages that sequences share are read-only: the written page is a
    sequence's own; length 0 has no current token: below.)"""
    args, _seqs, _H = _batch_case(_BATCH_CASES[case], monkeypatch)
    B = len(args[4])
    k_new, v_new = _rows(2, args)
    (out, k_pool, v_pool), (ref, k_ref, v_ref) = _written(args, k_new, v_new)
    np.testing.assert_array_equal(np.asarray(k_pool), np.asarray(k_ref))
    np.testing.assert_array_equal(np.asarray(v_pool), np.asarray(v_ref))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    changed = (np.asarray(k_pool) != args[1]).any(axis=(1, 3))  # (P, page)
    assert changed.sum() == B


# Where the new token lands: (lengths, table width, pages a block holds).
_WRITE_CASES = {
    # the token opens a page: (length - 1) % page == 0, blocks of 1 and 2
    "opens_a_page": ([9, 17, 33, 1], 5, 2),
    "opens_a_page_one_page_blocks": ([9, 17, 33, 1], 5, 1),
    # the last position the table has
    "last_position_of_the_table": ([40, 3], 5, 2),
    # a position past the table: nothing is written, the table is read
    "past_the_table": ([41, 48, 7], 5, 2),
    # no current token: nothing is written, zeros come back
    "length_0": ([0, 12, 0], 5, 2),
}


@pytest.mark.parametrize("case", list(_WRITE_CASES))
def test_paged_batch_kernel_write_positions(case, monkeypatch):
    args, _seqs, _H = _batch_case((*_WRITE_CASES[case], 4, "shuffled"),
                                  monkeypatch)
    q, k_pool, v_pool, tables, lengths = args
    page, cap = k_pool.shape[2], tables.shape[1] * k_pool.shape[2]
    k_new, v_new = _rows(3, args)
    # the reference: rows whose position the table has, scattered
    k_ref, v_ref = k_pool.copy(), v_pool.copy()
    for b in np.flatnonzero((lengths >= 1) & (lengths <= cap)):
        at = lengths[b] - 1
        k_ref[tables[b, at // page], :, at % page] = k_new[b]
        v_ref[tables[b, at // page], :, at % page] = v_new[b]
    out, k_got, v_got = _write(args, k_new, v_new)
    np.testing.assert_array_equal(np.asarray(k_got), k_ref)
    np.testing.assert_array_equal(np.asarray(v_got), v_ref)
    ref = paged_decode_attention_batch(
        jnp.asarray(q), jnp.asarray(k_ref), jnp.asarray(v_ref),
        jnp.asarray(tables), jnp.asarray(lengths))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert (np.asarray(out)[lengths == 0] == 0).all()


def test_empty_slots_write_the_one_dummy_page(monkeypatch):
    """The engine's empty slots: every table entry the dummy page, length
    1 with the current token. They all write the dummy page's first row,
    one after the other, and each attends over its own token alone."""
    args, _seqs, H = _batch_case(_BATCH_CASES["ragged"], monkeypatch)
    q, k_pool, v_pool, tables, lengths = args
    dummy = tables[0, -1]
    tables[1:3] = dummy
    lengths[1:3] = 1
    k_new, v_new = _rows(4, args)
    out, k_got, v_got = _write(args, k_new, v_new)
    out = np.asarray(out)
    assert np.isfinite(out).all()
    for b in (1, 2):        # softmax over one key: the value itself
        np.testing.assert_allclose(
            out[b], np.repeat(v_new[b], H // 4, axis=0), rtol=1e-6)
    # the dummy page holds the last writer's row; the live rows are kept
    np.testing.assert_array_equal(np.asarray(k_got)[dummy, :, 0], k_new[2])
    for b in (0, 3):
        np.testing.assert_array_equal(
            np.asarray(k_got)[tables[b, (lengths[b] - 1) // 8], :,
                              (lengths[b] - 1) % 8], k_new[b])


def test_float32_queries_over_bfloat16_pools(monkeypatch):
    """The SambaY call: float32 queries and rows, bfloat16 pools. The
    value stored, and attended, is the row rounded to the pools' type."""
    args, _seqs, _H = _batch_case(_BATCH_CASES["ragged"], monkeypatch)
    q, k_pool, v_pool, tables, lengths = args
    args = (q, jnp.asarray(k_pool, jnp.bfloat16),
            jnp.asarray(v_pool, jnp.bfloat16), tables, lengths)
    k_new, v_new = _rows(5, args)
    (out, k_got, v_got), (ref, k_ref, v_ref) = _written(args, k_new, v_new)
    assert k_got.dtype == jnp.bfloat16 and out.dtype == jnp.float32
    as_f32 = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731
    np.testing.assert_array_equal(as_f32(k_got), as_f32(k_ref))
    np.testing.assert_array_equal(as_f32(v_got), as_f32(v_ref))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def _scatters(jaxpr, shapes):
    """Scatter equations, at any depth of `jaxpr`, whose operand has one
    of `shapes`."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter") \
                and eqn.invars[0].aval.shape in shapes:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _scatters(sub, shapes)
    return found


@pytest.mark.parametrize("family", ["llama", "sambay"])
def test_a_decode_step_scatters_nothing_into_a_pool(family):
    """The one-token write is the kernel's: a decode step of either
    family holds no scatter on a buffer of a pool's shape (such a write,
    outside the kernel, made the compiler copy every pool whole, both
    ways, every step; PERF.md, PR 29)."""
    from ray_tpu.serve.llm_families import family_of

    if family == "llama":
        from ray_tpu.models.llama import LlamaConfig, LlamaModel

        cfg = LlamaConfig(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                          n_kv_heads=2, d_ff=128, max_seq_len=128,
                          dtype=jnp.float32, attention="reference",
                          remat=False)
        params = jax.eval_shape(lambda: LlamaModel(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    else:
        from ray_tpu.models.sambay import TINY_SAMBAY as cfg, SambaYModel

        params = jax.eval_shape(lambda: SambaYModel(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    fam = family_of(cfg, 96)
    B, pages, page = 3, 12, 16
    state = jax.eval_shape(lambda: fam.init_state(B, pages, page))
    i32 = jax.ShapeDtypeStruct((B,), jnp.int32)
    live = None if fam.rewinds else jax.ShapeDtypeStruct((B,), jnp.bool_)
    jaxpr = jax.make_jaxpr(fam.decode)(
        params, i32, i32, state, jax.ShapeDtypeStruct((B, 6), jnp.int32),
        i32, live)
    pools = {x.shape for x in jax.tree_util.tree_leaves(state)
             if x.shape[0] == pages}
    assert pools and not _scatters(jaxpr.jaxpr, pools)
    # (the probe finds one where there is one: the prompt's pages)
    if family == "llama":
        fresh = jax.eval_shape(fam.prefill, params,
                               jax.ShapeDtypeStruct((2, 32), jnp.int32),
                               jax.ShapeDtypeStruct((2,), jnp.int32))[1]
    else:   # (its prompts come a block a program from the host: the same
        # per-slot state for two rows, and the rows' K and V)
        kv = jax.ShapeDtypeStruct((2, cfg.kv_pairs, 32, 2 * cfg.head_dim),
                                  cfg.dtype)
        rows = jax.eval_shape(lambda: fam.init_state(2, pages, page))
        fresh = {"rings": rows["rings"], "mamba": rows["mamba"],
                 "cache": (kv, kv)}
    written = jax.make_jaxpr(fam.write_prompt)(
        state, fresh, jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((2, fam.prompt_pages(32, page)), jnp.int32))
    assert _scatters(written.jaxpr, pools)
