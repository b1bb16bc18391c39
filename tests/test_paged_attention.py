"""Paged decode attention kernel + page allocator (vLLM block-table idea,
TPU pallas scalar-prefetch kernel; reference serves LLMs through
vLLM-style engines whose core mechanism this is)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops.paged_attention import (  # noqa: E402
    PageAllocator, paged_decode_attention)


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


@pytest.mark.parametrize("length", [1, 7, 16, 37])
def test_paged_matches_dense(length):
    H, Hkv, D, page = 8, 4, 32, 16
    rng = np.random.default_rng(0)
    q = rng.standard_normal((H, D)).astype(np.float32)
    keys = rng.standard_normal((length, Hkv, D)).astype(np.float32)
    values = rng.standard_normal((length, Hkv, D)).astype(np.float32)

    # Scatter the sequence into a shuffled page pool (P, Hkv, page, D).
    npages = -(-length // page)
    pool_pages = 8
    order = rng.permutation(pool_pages)[:npages]
    k_pool = np.zeros((pool_pages, Hkv, page, D), np.float32)
    v_pool = np.zeros((pool_pages, Hkv, page, D), np.float32)
    for i, pg in enumerate(order):
        chunk = keys[i * page:(i + 1) * page]
        k_pool[pg, :, :len(chunk)] = chunk.transpose(1, 0, 2)
        v_pool[pg, :, :len(chunk)] = \
            values[i * page:(i + 1) * page].transpose(1, 0, 2)
    table = np.concatenate([order, np.full(4 - npages, order[-1])]) \
        if npages < 4 else order[:4]

    out = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(table, jnp.int32), jnp.asarray(length))
    ref = _ref_attention(q, keys, values, groups=H // Hkv)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


def test_paged_batch_vmap():
    """vmap over sequences with DIFFERENT lengths/page tables — the
    continuous-batching decode shape."""
    H, Hkv, D, page = 4, 4, 16, 8
    B, pool_pages, npages = 3, 12, 3
    rng = np.random.default_rng(1)
    lengths = np.array([5, 17, 24], np.int32)
    k_pool = rng.standard_normal((pool_pages, Hkv, page, D)).astype(np.float32)
    v_pool = rng.standard_normal((pool_pages, Hkv, page, D)).astype(np.float32)
    tables = np.array([[0, 1, 2], [3, 4, 5], [6, 7, 8]], np.int32)
    qs = rng.standard_normal((B, H, D)).astype(np.float32)

    batched = jax.vmap(paged_decode_attention,
                       in_axes=(0, None, None, 0, 0))
    out = batched(jnp.asarray(qs), jnp.asarray(k_pool), jnp.asarray(v_pool),
                  jnp.asarray(tables), jnp.asarray(lengths))
    assert out.shape == (B, H, D)
    for b in range(B):
        ln = int(lengths[b])
        keys = k_pool[tables[b]].transpose(0, 2, 1, 3).reshape(
            -1, Hkv, D)[:ln]
        values = v_pool[tables[b]].transpose(0, 2, 1, 3).reshape(
            -1, Hkv, D)[:ln]
        ref = _ref_attention(qs[b], keys, values, groups=1)
        np.testing.assert_allclose(np.asarray(out[b]), ref,
                                   rtol=2e-4, atol=2e-4)


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


# What the batched kernel branches on: (lengths, table width, pages a
# block may hold, Hkv, how each sequence's pages are picked from the pool).
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
}


@pytest.mark.parametrize("case", list(_BATCH_CASES))
def test_paged_batch_kernel_matches_dense(case, monkeypatch):
    """The batched kernel (one grid step a sequence, a loop over its live
    pages inside) against the dense reference: the exact shape the paged
    LLM engine uses."""
    from ray_tpu.ops import paged_attention

    lengths, NP, pages_per_block, Hkv, picking = _BATCH_CASES[case]
    H, D, page, pool_pages = 8, 32, 8, 32
    B = len(lengths)
    rng = np.random.default_rng(1)
    if picking == "shared":
        tables = np.array([[20, 11, 5, 25 + b, 28 - b] for b in range(B)],
                          np.int32)
    else:
        # past the live pages: one valid index, as the engine's dummy page
        free = list(rng.permutation(pool_pages))
        tables = np.full((B, NP), free.pop(), np.int32)
        for b, L in enumerate(lengths):
            for i in range(-(-L // page)):
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

    out = np.asarray(paged_attention.paged_decode_attention_batch(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)))
    assert np.isfinite(out).all()
    for b, L in enumerate(lengths):
        if L == 0:
            continue        # an empty slot's row is ignored by the engine
        ref = _ref_attention(q[b], seqs[b][0], seqs[b][1],
                             groups=H // Hkv)
        np.testing.assert_allclose(out[b], ref, rtol=2e-4, atol=2e-4)
