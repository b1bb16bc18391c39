"""Paged decode attention: single-query attention over a block-table KV
pool (the vLLM "PagedAttention" idea, TPU-shaped).

The KV cache is a shared POOL of fixed-size pages; each sequence owns a
page table of pool indices. HBM is allocated by total resident tokens,
not `max_len x slots` — the round-1 engine's admitted waste
(reference: the reference serves LLMs through vLLM-style external
engines whose core trick is exactly this block table).

The kernel attends over scattered pages without ever materializing a
contiguous per-sequence cache, accumulating an online softmax across
pages (same recurrence as ops/attention.py's flash kernel), with page
tables and lengths scalar-prefetched into SMEM
(PrefetchScalarGridSpec).

It costs what the resident tokens cost: one grid step a sequence, and
inside it a loop that ends at the sequence's last live page. The pools
stay in HBM; a live page is copied whole (all KV heads, one contiguous
transfer) into a double-buffered VMEM scratch, the next block's copies
in flight while the current block is computed.

Handed the step's new K and V rows, the kernel also WRITES them: the
pools are aliased from input to output, the row is put into the copy of
the sequence's last live page in VMEM, and that one page goes back. A
decode step's state then never changes form between the write and the
read, so the compiler has no reason to copy a pool (a one-token scatter
outside the kernel made it carry every pool token-major through the
decode loop and copy it whole, both ways, every step; PERF.md, PR 29).

On CPU (tests) the kernel runs in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _interpret_mode() -> bool:
    return jax.default_backend() != "tpu"


def _online_softmax_update(start, length, q, k, v, m_prev, l_prev, acc_prev,
                           *, sm_scale: float):
    """One block of the online-softmax recurrence. `k`/`v` hold the
    cached tokens `start`, `start + 1`, ...; those at or past `length`
    are masked.

    Pure function of values: callers own the scratch-ref IO. Every dot is
    a plain 2D (G, D) x (tokens, D) matmul: Mosaic lowers 2D dots onto
    the MXU but rejects the batched `hgd,thd` einsum form ("batch dims
    must be equal" on real TPU; caught by scripts/tpu_kernel_sweep.py
    on-chip validation). Returns (m_new, l_new, acc_new).
    """
    # scores[g, t] = q[g, :] . k[t, :]  — 2D dot, MXU-safe
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ()))) * sm_scale
    token_idx = start + jax.lax.broadcasted_iota(
        jnp.int32, scores.shape, 1)
    scores = jnp.where(token_idx < length, scores, _NEG_INF)

    m_cur = jnp.max(scores, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)                 # (G, tokens)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))  # (G, D)
    return m_new, l_new, acc_prev * alpha + pv


def _normalized(l, acc):
    """Final softmax normalization with the all-masked guard (l == 0)."""
    return acc / jnp.where(l == 0.0, 1.0, l)


# VMEM the K and V page buffers of the batched kernel may take together
# (two slots each, so that one block is copied while one is computed).
_KV_VMEM_BYTES = 4 * 1024 * 1024


def _pages_per_block(page_bytes: int, table_pages: int) -> int:
    """Pool pages one block of the batched kernel holds: as many as the
    VMEM budget allows for 2 pools x 2 slots, at most a whole table."""
    return max(1, min(table_pages, _KV_VMEM_BYTES // (4 * page_bytes)))


def _paged_decode_batch_kernel(length_ref, page_table_ref,  # scalar prefetch
                               *refs, page_size: int, pages_per_block: int,
                               table_pages: int, sm_scale: float,
                               writes: bool):
    # Grid: (B,), one step a sequence. The pools stay in HBM; the step
    # loops over the sequence's LIVE blocks of `pages_per_block` pages,
    # copying each live page (all KV heads: one contiguous transfer in
    # the (P, Hkv, page, D) layout) into one of two VMEM slots while the
    # other slot is computed. The last block of a sequence starts the
    # first block of the next one, so only the very first copy of a call
    # is waited for with nothing to compute. `slot_ref` (SMEM) carries the
    # slot that copy went to from one grid step to the next.
    if writes:
        # The pools are outputs aliased to the inputs: read and written
        # through the one (output) ref, so that a read after the write
        # sees it on the chip and in interpret mode alike.
        (q_ref, k_new_ref, v_new_ref, _, _, o_ref, k_hbm, v_hbm,
         k_buf, v_buf, sems, slot_ref,
         m_scratch, l_scratch, acc_scratch) = refs
    else:
        (q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, slot_ref,
         m_scratch, l_scratch, acc_scratch) = refs
    b = pl.program_id(0)
    num_seqs = pl.num_programs(0)
    num_heads = q_ref.shape[1]
    block_tokens = page_size * pages_per_block
    table_tokens = table_pages * page_size

    def length_of(seq):
        return jnp.minimum(length_ref[seq], table_tokens)

    length = length_of(b)
    num_blocks = pl.cdiv(length, block_tokens)

    def for_live_pages(seq, blk, slot, act):
        """`act` on the K and the V copy of every live page of block
        `blk` of sequence `seq`, into buffer `slot`."""
        first = blk * pages_per_block
        live = jnp.clip(pl.cdiv(length_of(seq), page_size) - first,
                        0, pages_per_block)

        def page(j, carry):
            src = page_table_ref[seq * table_pages + first + j]
            act(pltpu.make_async_copy(
                k_hbm.at[src], k_buf.at[slot, j], sems.at[0, slot]))
            act(pltpu.make_async_copy(
                v_hbm.at[src], v_buf.at[slot, j], sems.at[1, slot]))
            return carry

        jax.lax.fori_loop(0, live, page, None)

    def start(seq, blk, slot):
        for_live_pages(seq, blk, slot, lambda copy: copy.start())

    def wait(seq, blk, slot):
        for_live_pages(seq, blk, slot, lambda copy: copy.wait())

    if writes:
        # The new token: position `length - 1` of the sequence, in its
        # last live page. A position outside the table (the clamp above)
        # or a length of 0 writes nothing.
        new_pos = length_ref[b] - 1
        new_page = new_pos // page_size
        puts = jnp.logical_and(new_pos >= 0, new_pos < table_tokens)

        def for_new_page(slot, j, act):
            """`act` on the copies of page `j` of buffer `slot` back to
            the pools, as the page of the new token."""
            dst = page_table_ref[b * table_pages + new_page]
            act(pltpu.make_async_copy(
                k_buf.at[slot, j], k_hbm.at[dst], sems.at[0, 2]))
            act(pltpu.make_async_copy(
                v_buf.at[slot, j], v_hbm.at[dst], sems.at[1, 2]))

    @pl.when(b == 0)
    def _first():
        # Pages past a sequence's length are never copied, and masked
        # scores give them p == 0 exactly; what they multiply must still
        # be finite, so the slots start as zeros, not as whatever VMEM
        # held (after that they only ever hold pool pages).
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        start(0, 0, 0)

    first_slot = slot_ref[0]
    m_scratch[...] = jnp.full_like(m_scratch, _NEG_INF)
    l_scratch[...] = jnp.zeros_like(l_scratch)
    acc_scratch[...] = jnp.zeros_like(acc_scratch)

    def block(blk, carry):
        slot = (first_slot + blk) % 2
        last = blk + 1 == num_blocks

        @pl.when(jnp.logical_or(jnp.logical_not(last), b + 1 < num_seqs))
        def _next():
            # this sequence's next block, or the first of the next one
            start(jnp.where(last, b + 1, b), jnp.where(last, 0, blk + 1),
                  1 - slot)

        wait(b, blk, slot)
        if writes:
            new_j = new_page - blk * pages_per_block

            @pl.when(jnp.logical_and(last, puts))
            def _put():
                # The row goes into the page's copy by a select on the
                # token's index (no store of part of a packed tile), and
                # the page goes back while the block is computed.
                here = jax.lax.broadcasted_iota(
                    jnp.int32, k_buf.shape[-2:], 0) == new_pos % page_size
                for h in range(num_heads):
                    for buf, new in ((k_buf, k_new_ref), (v_buf, v_new_ref)):
                        buf[slot, new_j, h] = jnp.where(
                            here, new[0, pl.ds(h, 1), :],
                            buf[slot, new_j, h])
                for_new_page(slot, new_j, lambda copy: copy.start())

        # Dots over the whole block, dead pages too (masked): a dot for
        # every 1, 2 or 4 pages spared those and was still slower in all
        # three shapes measured, its fixed cost a head is most of it.
        for h in range(num_heads):      # static: unrolled at trace time
            k = k_buf[slot, :, h].reshape(block_tokens, -1)
            v = v_buf[slot, :, h].reshape(block_tokens, -1)
            m_new, l_new, acc_new = _online_softmax_update(
                blk * block_tokens, length,
                q_ref[0, h].astype(jnp.float32),       # (G, D)
                k.astype(jnp.float32),                 # (tokens, D)
                v.astype(jnp.float32),
                m_scratch[h], l_scratch[h], acc_scratch[h],
                sm_scale=sm_scale)
            m_scratch[h] = m_new
            l_scratch[h] = l_new
            acc_scratch[h] = acc_new

        if writes:
            # before the slot is filled again, and before the call ends
            @pl.when(jnp.logical_and(last, puts))
            def _put_done():
                for_new_page(slot, new_j, lambda copy: copy.wait())
        return carry

    jax.lax.fori_loop(0, num_blocks, block, None)

    # A sequence of length 0 ran no block, so nothing started its
    # successor's first copy; both slots are free, take the same one.
    @pl.when(jnp.logical_and(num_blocks == 0, b + 1 < num_seqs))
    def _empty():
        start(b + 1, 0, first_slot)

    slot_ref[0] = (first_slot + num_blocks) % 2
    o_ref[0] = _normalized(l_scratch[...],
                           acc_scratch[...]).astype(o_ref.dtype)


def paged_decode_attention_batch(q, k_pool, v_pool, page_tables, lengths,
                                 *, k_new=None, v_new=None,
                                 sm_scale: float | None = None):
    """Batched single-token decode attention over paged KV.

    One kernel for every slot of a continuous-batching engine. Its work
    follows the tokens resident in the cache: a sequence costs its live
    pages (`ceil(length / page_size)`, whatever the table's width), a
    sequence of length 0 nothing. How many pages one block holds comes
    from the shapes (`_pages_per_block`), not from the caller.

    q:           (B, H, D) one query per sequence
    k/v_pool:    (P, Hkv, page_size, D) pools SHARED by all sequences:
                 head-then-page minor layout, so page p is one contiguous
                 Hkv x page_size x D block, copied whole, and each (head,
                 page) a (page_size, D) tile
    page_tables: (B, NP) int32 pool indices per sequence (entries past
                 the live length are never read)
    lengths:     (B,) int32 valid token counts (incl. current tokens);
                 a row of length 0 returns zeros, one past NP * page_size
                 attends over the table's NP * page_size
    k/v_new:     (B, Hkv, D), or none: the current tokens' K and V, which
                 the call then writes before it attends: row b to page
                 `page_tables[b, (lengths[b] - 1) // page_size]`, offset
                 `(lengths[b] - 1) % page_size` (nothing where that is
                 outside the table), rounded to the pools' type. That
                 page must be sequence b's own.
    Returns (B, H, D); with k/v_new, (out, k_pool, v_pool): the pools
    updated in place (donate them, or the caller pays a copy).
    """
    _, Hkv, page_size, D = k_pool.shape
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    new = None if k_new is None else (k_new.astype(k_pool.dtype),
                                      v_new.astype(v_pool.dtype))
    return _paged_decode_batch_call(
        q, k_pool, v_pool, page_tables, lengths, new, sm_scale=sm_scale,
        pages_per_block=_pages_per_block(
            Hkv * page_size * D * k_pool.dtype.itemsize,
            page_tables.shape[1]),
        interpret=_interpret_mode())


# jit, so that the layers of a model share ONE lowering of the kernel:
# its body (the heads are unrolled) takes a second or two to lower on the
# chip's host, and a decode program lowered it once a layer, in every
# process's set-up (30-39 s for 16 layers; my chip runs, PR 25). `new` is
# the rows to write or None: one lowering for each of the two.
@functools.partial(jax.jit, static_argnames=("sm_scale", "pages_per_block",
                                             "interpret"))
def _paged_decode_batch_call(q, k_pool, v_pool, page_tables, lengths, new, *,
                             sm_scale: float, pages_per_block: int,
                             interpret: bool):
    B, H, D = q.shape
    P, Hkv, page_size, _ = k_pool.shape
    groups = H // Hkv
    table_pages = page_tables.shape[1]
    writes = new is not None
    buf = (2, pages_per_block, Hkv, page_size, D)
    row = pl.BlockSpec((1, Hkv, groups, D), lambda b, ln, pt: (b, 0, 0, 0))
    new_row = pl.BlockSpec((1, Hkv, D), lambda b, ln, pt: (b, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = jax.ShapeDtypeStruct((B, Hkv, groups, D), q.dtype)
    pool = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[row] + [new_row] * (2 * writes) + [in_hbm, in_hbm],
        out_specs=[row, in_hbm, in_hbm] if writes else row,
        scratch_shapes=[
            pltpu.VMEM(buf, k_pool.dtype),
            pltpu.VMEM(buf, v_pool.dtype),
            # (K | V, the two slots' reads and the new page's write)
            pltpu.SemaphoreType.DMA((2, 3)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((Hkv, groups, 1), jnp.float32),
            pltpu.VMEM((Hkv, groups, 1), jnp.float32),
            pltpu.VMEM((Hkv, groups, D), jnp.float32),
        ],
    )
    result = pl.pallas_call(
        functools.partial(_paged_decode_batch_kernel, page_size=page_size,
                          pages_per_block=pages_per_block,
                          table_pages=table_pages, sm_scale=sm_scale,
                          writes=writes),
        grid_spec=grid_spec,
        out_shape=[out, pool(k_pool), pool(v_pool)] if writes else out,
        # (operands count from the scalar-prefetched two)
        input_output_aliases={5: 1, 6: 2} if writes else {},
        # The slot handed from one sequence to the next makes the grid a
        # sequence, not a set.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lengths.astype(jnp.int32), page_tables.astype(jnp.int32).reshape(-1),
      q.reshape(B, Hkv, groups, D), *(new or ()), k_pool, v_pool)
    if writes:
        return (result[0].reshape(B, H, D), *result[1:])
    return result.reshape(B, H, D)


# ---------------------------------------------------------------------------
# Latent pages: one pool a layer, read once, key and value both
# ---------------------------------------------------------------------------
#
# A latent-attention layer caches ONE row a token for all its heads: the
# normed latent (`d_value` wide) and behind it the rotated key they share,
# padded with zeros to whole lanes (`W`: 512 + 64 -> 640).  With the
# up-projections absorbed into the query and into the output, a head's
# score is its query against the whole row and its output the softmax over
# the row's first `d_value` columns: every head reads the same page, so a
# page is copied ONCE and serves as key and as value.  The K/V kernel above
# takes two pools of one width; handed the latent twice it would copy every
# page twice.  What is shared with it: the order of the copies (a block of
# live pages into one of two VMEM slots while the other is computed, the
# last block of a sequence starting the first of the next), the online
# softmax's recurrence, and the step's row written into the copy of the
# sequence's last live page, which goes back in place.
#
# The query's rows and the softmax's weights enter their products as two
# bfloat16 terms (rows side by side: 2 x heads rows against the page, one
# product each), so that the latent is multiplied as it is stored and never
# widened to float32: sixteen heads over a 640-wide key are 30 operations
# a byte, which float32 products would make the kernel's bound.

_LATENT_VMEM_BYTES = 3 * 1024 * 1024


def _against_rows(a, rows, contract: int):
    """a (H, n) float32 times the cached `rows` over their axis
    `contract`, accumulated in float32.  Rows in bfloat16 are multiplied as
    they are: `a` enters as two bfloat16 terms (its leading 8 bits and
    what they left, one under the other: ONE product of 2 H rows)."""
    dims = (((1,), (contract,)), ((), ()))
    if rows.dtype == jnp.float32:
        return jax.lax.dot_general(a, rows, dims)
    # (the leading bits by a mask, as `models/sambay._two_terms` takes
    # them: a round trip through bfloat16 is the compiler's to elide)
    hi = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(a, jnp.uint32) & jnp.uint32(0xFFFF0000),
        jnp.float32)
    terms = jnp.concatenate([hi, a - hi], axis=0).astype(jnp.bfloat16)
    out = jax.lax.dot_general(terms, rows, dims,
                              preferred_element_type=jnp.float32)
    return out[: a.shape[0]] + out[a.shape[0]:]


def _paged_latent_kernel(length_ref, page_table_ref,    # scalar prefetch
                         q_ref, new_ref, _, o_ref, pool, buf, sems,
                         slot_ref, m_scratch, l_scratch, acc_scratch,
                         *, page_size: int, pages_per_block: int,
                         table_pages: int, d_value: int, sm_scale: float):
    # Grid: (B,), one step a sequence; `pool` is the output aliased to the
    # input pool (read and written through the one ref).
    b = pl.program_id(0)
    num_seqs = pl.num_programs(0)
    block_tokens = page_size * pages_per_block
    table_tokens = table_pages * page_size

    def length_of(seq):
        return jnp.minimum(length_ref[seq], table_tokens)

    length = length_of(b)
    num_blocks = pl.cdiv(length, block_tokens)

    def for_live_pages(seq, blk, slot, act):
        first = blk * pages_per_block
        live = jnp.clip(pl.cdiv(length_of(seq), page_size) - first,
                        0, pages_per_block)

        def page(j, carry):
            src = page_table_ref[seq * table_pages + first + j]
            act(pltpu.make_async_copy(pool.at[src], buf.at[slot, j],
                                      sems.at[slot]))
            return carry

        jax.lax.fori_loop(0, live, page, None)

    def start(seq, blk, slot):
        for_live_pages(seq, blk, slot, lambda copy: copy.start())

    def wait(seq, blk, slot):
        for_live_pages(seq, blk, slot, lambda copy: copy.wait())

    # The new token: position `length - 1`, in the last live page.  A
    # position outside the table or a length of 0 writes nothing.
    new_pos = length_ref[b] - 1
    new_page = new_pos // page_size
    puts = jnp.logical_and(new_pos >= 0, new_pos < table_tokens)

    def new_page_back(slot, j):
        return pltpu.make_async_copy(
            buf.at[slot, j],
            pool.at[page_table_ref[b * table_pages + new_page]], sems.at[2])

    @pl.when(b == 0)
    def _first():
        # pages past a length are never copied and their weights are 0
        # exactly; what those multiply must still be finite
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        start(0, 0, 0)

    first_slot = slot_ref[0]
    m_scratch[...] = jnp.full_like(m_scratch, _NEG_INF)
    l_scratch[...] = jnp.zeros_like(l_scratch)
    acc_scratch[...] = jnp.zeros_like(acc_scratch)
    q = q_ref[0].astype(jnp.float32)                        # (H, W)

    def block(blk, carry):
        slot = (first_slot + blk) % 2
        last = blk + 1 == num_blocks

        @pl.when(jnp.logical_or(jnp.logical_not(last), b + 1 < num_seqs))
        def _next():
            start(jnp.where(last, b + 1, b), jnp.where(last, 0, blk + 1),
                  1 - slot)

        wait(b, blk, slot)
        new_j = new_page - blk * pages_per_block

        @pl.when(jnp.logical_and(last, puts))
        def _put():
            here = jax.lax.broadcasted_iota(
                jnp.int32, buf.shape[-2:], 0) == new_pos % page_size
            buf[slot, new_j] = jnp.where(here, new_ref[0], buf[slot, new_j])
            new_page_back(slot, new_j).start()

        rows = buf[slot].reshape(block_tokens, -1)          # (tokens, W)
        s = _against_rows(q, rows, 1) * sm_scale            # (H, tokens)
        token_idx = blk * block_tokens + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(token_idx < length, s, _NEG_INF)
        m_prev = m_scratch[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        pv = _against_rows(p, rows[:, :d_value], 0)         # (H, d_value)
        m_scratch[...] = m_new
        l_scratch[...] = alpha * l_scratch[...] + jnp.sum(
            p, axis=-1, keepdims=True)
        acc_scratch[...] = acc_scratch[...] * alpha + pv

        # before the slot is filled again, and before the call ends
        @pl.when(jnp.logical_and(last, puts))
        def _put_done():
            new_page_back(slot, new_j).wait()
        return carry

    jax.lax.fori_loop(0, num_blocks, block, None)

    @pl.when(jnp.logical_and(num_blocks == 0, b + 1 < num_seqs))
    def _empty():
        start(b + 1, 0, first_slot)

    slot_ref[0] = (first_slot + num_blocks) % 2
    o_ref[0] = _normalized(l_scratch[...],
                           acc_scratch[...]).astype(o_ref.dtype)


def paged_latent_attention_batch(q, pool, page_tables, lengths, new, *,
                                 d_value: int, sm_scale: float):
    """Batched single-token decode attention of a latent-attention layer
    over its paged latent cache; the call writes the step's rows first.

    q:           (B, H, W) float32: each head's absorbed query, laid out
                 as a cached row is (zeros where the row is padding)
    pool:        (P, page_size, W) the layer's ONE pool, shared by all
                 heads: a page is one contiguous page_size x W block
    page_tables: (B, NP) int32; lengths: (B,) int32, the current token
                 counted (a row of length 0 returns zeros and writes
                 nothing), as `paged_decode_attention_batch` reads them
    new:         (B, W) the current tokens' rows, written to page
                 `page_tables[b, (lengths[b] - 1) // page_size]` before the
                 call attends (that page must be sequence b's own)
    Returns (out (B, H, d_value) float32: the softmax over the rows' first
    `d_value` columns, the pool updated in place: donate it)."""
    _, page_size, W = pool.shape
    return _paged_latent_call(
        q, pool, page_tables, lengths, new.astype(pool.dtype)[:, None],
        d_value=d_value, sm_scale=sm_scale,
        pages_per_block=max(1, min(
            page_tables.shape[1], _LATENT_VMEM_BYTES
            // (2 * page_size * W * pool.dtype.itemsize))),
        interpret=_interpret_mode())


# (jit: the layers of a model share one lowering, as the kernel above)
@functools.partial(jax.jit, static_argnames=(
    "d_value", "sm_scale", "pages_per_block", "interpret"))
def _paged_latent_call(q, pool, page_tables, lengths, new, *, d_value: int,
                       sm_scale: float, pages_per_block: int,
                       interpret: bool):
    B, H, W = q.shape
    _, page_size, _ = pool.shape
    row = lambda *shape: pl.BlockSpec(  # noqa: E731
        (1, *shape), lambda b, ln, pt: (b, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    out, pool = pl.pallas_call(
        functools.partial(_paged_latent_kernel, page_size=page_size,
                          pages_per_block=pages_per_block,
                          table_pages=page_tables.shape[1],
                          d_value=d_value, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[row(H, W), row(1, W), in_hbm],
            out_specs=[row(H, d_value), in_hbm],
            scratch_shapes=[
                pltpu.VMEM((2, pages_per_block, page_size, W), pool.dtype),
                # (the two slots' reads and the new page's write)
                pltpu.SemaphoreType.DMA((3,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, d_value), jnp.float32),
            ]),
        out_shape=[jax.ShapeDtypeStruct((B, H, d_value), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # (operands count from the scalar-prefetched two)
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lengths.astype(jnp.int32), page_tables.astype(jnp.int32).reshape(-1),
      q, new, pool)
    return out, pool


class PageAllocator:
    """Host-side free-list allocator for KV pool pages (one per engine).

    Parity target: vLLM's block manager — sequences grow page by page;
    freeing a sequence returns its pages to the pool. Pure Python (the
    allocator runs in the serving loop, not inside jit)."""

    def __init__(self, num_pages: int, page_size: int):
        self.num_pages = num_pages
        self.page_size = page_size
        self._free = list(range(num_pages - 1, -1, -1))
        self._owned: dict[str, list[int]] = {}

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def pages_needed(self, num_tokens: int) -> int:
        return -(-num_tokens // self.page_size)

    def allocate(self, seq_id: str, num_tokens: int) -> list[int]:
        """Reserve pages so `seq_id` can hold num_tokens total; grows the
        existing reservation. Raises MemoryError when the pool is dry
        (callers queue the request — admission control)."""
        owned = self._owned.setdefault(seq_id, [])
        need = self.pages_needed(num_tokens) - len(owned)
        if need > len(self._free):
            raise MemoryError(
                f"KV pool exhausted: need {need} pages, {len(self._free)} free")
        for _ in range(max(0, need)):
            owned.append(self._free.pop())
        return list(owned)

    def table(self, seq_id: str, npages: int) -> "jnp.ndarray":
        """Fixed-width page table (padded with a valid dummy index so the
        kernel's out-of-range grid steps stay in bounds; masking by
        `length` makes their scores irrelevant)."""
        return jnp.asarray(self.table_row(seq_id, npages))

    def table_row(self, seq_id: str, npages: int) -> np.ndarray:
        """`table`, on the host (the engine's mirror of a slot's row)."""
        owned = self._owned.get(seq_id, [])
        pad = owned[-1] if owned else 0
        return np.asarray((owned + [pad] * npages)[:npages], np.int32)

    def free(self, seq_id: str) -> None:
        self._free.extend(reversed(self._owned.pop(seq_id, [])))
