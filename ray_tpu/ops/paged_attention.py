"""Paged decode attention: single-query attention over a block-table KV
pool (the vLLM "PagedAttention" idea, TPU-shaped).

The KV cache is a shared POOL of fixed-size pages; each sequence owns a
page table of pool indices. HBM is allocated by total resident tokens,
not `max_len x slots` — the round-1 engine's admitted waste
(reference: the reference serves LLMs through vLLM-style external
engines whose core trick is exactly this block table).

Both kernels attend over scattered pages without ever materializing a
contiguous per-sequence cache, accumulating an online softmax across
pages (same recurrence as ops/attention.py's flash kernel), with page
tables and lengths scalar-prefetched into SMEM
(PrefetchScalarGridSpec).

The batched kernel, the one the engine runs, costs what the resident
tokens cost: one grid step a sequence, and inside it a loop that ends at
the sequence's last live page. The pools stay in HBM; a live page is
copied whole (all KV heads, one contiguous transfer) into a
double-buffered VMEM scratch, the next block's copies in flight while
the current block is computed. The single-sequence kernel (tests only)
still has one page per grid step, dereferenced by the index_map.

On CPU (tests) the kernel runs in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _interpret_mode() -> bool:
    return jax.default_backend() != "tpu"


def _online_softmax_update(start, length, q, k, v, m_prev, l_prev, acc_prev,
                           *, sm_scale: float):
    """One block of the online-softmax recurrence, shared by BOTH paged
    kernels (single-sequence, batched) so a numerics change cannot
    silently miss one of them. `k`/`v` hold the cached tokens `start`,
    `start + 1`, ...; those at or past `length` are masked.

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


def _online_softmax_page_step(pi, num_page_steps, length, q, k, v,
                              o_write, m_scratch, l_scratch, acc_scratch,
                              *, page_size: int, sm_scale: float):
    """One grid step of the single-sequence kernel over whole-scratch
    refs. pi: page-step program id; q: (G, D); k/v: (page, D); o_write:
    callback writing the normalized (G, D) output on the last step."""
    @pl.when(pi == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, _NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    m_new, l_new, acc_new = _online_softmax_update(
        pi * page_size, length, q, k, v, m_scratch[...], l_scratch[...],
        acc_scratch[...], sm_scale=sm_scale)
    m_scratch[...] = m_new
    l_scratch[...] = l_new
    acc_scratch[...] = acc_new

    @pl.when(pi == num_page_steps - 1)
    def _finish():
        o_write(_normalized(l_scratch[...], acc_scratch[...]))


def _paged_decode_kernel(page_table_ref, length_ref,  # scalar prefetch
                         q_ref, k_ref, v_ref, o_ref,
                         m_scratch, l_scratch, acc_scratch,
                         *, page_size: int, num_pages: int, groups: int,
                         sm_scale: float):
    # Grid: (Hkv, npages)
    pi = pl.program_id(1)

    def write(out):
        o_ref[0] = out.astype(o_ref.dtype)

    _online_softmax_page_step(
        pi, pl.num_programs(1), length_ref[0],
        q_ref[0].astype(jnp.float32),           # (G, D)
        k_ref[0, 0].astype(jnp.float32),        # (page, D)
        v_ref[0, 0].astype(jnp.float32),
        write, m_scratch, l_scratch, acc_scratch,
        page_size=page_size, sm_scale=sm_scale)


def paged_decode_attention(q, k_pool, v_pool, page_table, length,
                           *, sm_scale: float | None = None):
    """Single-token decode attention over paged KV.

    q:          (H, D) query for ONE sequence's current token
    k_pool/v_pool: (P, Hkv, page_size, D) shared pools — head-then-page
                minor layout so each (head, page) block is a contiguous
                (page, D) tile (Mosaic requires the last two block dims
                to tile as (sublane, lane))
    page_table: (NP,) int32 pool indices owned by this sequence (entries
                past the live length may be arbitrary valid indices)
    length:     () int32 valid token count (incl. the current token,
                whose K/V must already be written to the pool)
    Returns (H, D). vmap over sequences for a batch.
    """
    H, D = q.shape
    P, Hkv, page_size, _ = k_pool.shape
    groups = H // Hkv
    npages = page_table.shape[0]
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)

    q3 = q.reshape(Hkv, groups, D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Hkv, npages),
        in_specs=[
            pl.BlockSpec((1, groups, D), lambda h, i, pt, ln: (h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, D),
                         lambda h, i, pt, ln: (pt[i], h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, D),
                         lambda h, i, pt, ln: (pt[i], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, groups, D),
                               lambda h, i, pt, ln: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((groups, 1), jnp.float32),
            pltpu.VMEM((groups, 1), jnp.float32),
            pltpu.VMEM((groups, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, page_size=page_size,
                          num_pages=npages, groups=groups,
                          sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, groups, D), q.dtype),
        interpret=_interpret_mode(),
    )(page_table.astype(jnp.int32), length.reshape(1).astype(jnp.int32),
      q3, k_pool, v_pool)
    return out.reshape(H, D)


# VMEM the K and V page buffers of the batched kernel may take together
# (two slots each, so that one block is copied while one is computed).
_KV_VMEM_BYTES = 4 * 1024 * 1024


def _pages_per_block(page_bytes: int, table_pages: int) -> int:
    """Pool pages one block of the batched kernel holds: as many as the
    VMEM budget allows for 2 pools x 2 slots, at most a whole table."""
    return max(1, min(table_pages, _KV_VMEM_BYTES // (4 * page_bytes)))


def _paged_decode_batch_kernel(length_ref, page_table_ref,  # scalar prefetch
                               q_ref, k_hbm, v_hbm, o_ref,
                               k_buf, v_buf, sems, slot_ref,
                               m_scratch, l_scratch, acc_scratch,
                               *, page_size: int, pages_per_block: int,
                               table_pages: int, sm_scale: float):
    # Grid: (B,), one step a sequence. The pools stay in HBM; the step
    # loops over the sequence's LIVE blocks of `pages_per_block` pages,
    # copying each live page (all KV heads: one contiguous transfer in
    # the (P, Hkv, page, D) layout) into one of two VMEM slots while the
    # other slot is computed. The last block of a sequence starts the
    # first block of the next one, so only the very first copy of a call
    # is waited for with nothing to compute. `slot_ref` (SMEM) carries the
    # slot that copy went to from one grid step to the next.
    b = pl.program_id(0)
    num_seqs = pl.num_programs(0)
    num_heads = q_ref.shape[1]
    block_tokens = page_size * pages_per_block
    length = length_ref[b]
    num_blocks = pl.cdiv(length, block_tokens)

    def for_live_pages(seq, blk, slot, act):
        """`act` on the K and the V copy of every live page of block
        `blk` of sequence `seq`, into buffer `slot`."""
        first = blk * pages_per_block
        live = jnp.clip(pl.cdiv(length_ref[seq], page_size) - first,
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
                                 *, sm_scale: float | None = None):
    """Batched single-token decode attention over paged KV.

    One kernel for every slot of a continuous-batching engine. Its work
    follows the tokens resident in the cache: a sequence costs its live
    pages (`ceil(length / page_size)`, whatever the table's width), a
    sequence of length 0 nothing. How many pages one block holds comes
    from the shapes (`_pages_per_block`), not from the caller.

    q:           (B, H, D) one query per sequence
    k/v_pool:    (P, Hkv, page_size, D) pools SHARED by all sequences:
                 page p is one contiguous Hkv x page_size x D block, and
                 is copied whole (see paged_decode_attention for the
                 layout)
    page_tables: (B, NP) int32 pool indices per sequence (entries past
                 the live length are never read)
    lengths:     (B,) int32 valid token counts (incl. current tokens),
                 at most NP * page_size; a row of length 0 returns zeros
    Returns (B, H, D).
    """
    _, Hkv, page_size, D = k_pool.shape
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    return _paged_decode_batch_call(
        q, k_pool, v_pool, page_tables, lengths, sm_scale=sm_scale,
        pages_per_block=_pages_per_block(
            Hkv * page_size * D * k_pool.dtype.itemsize,
            page_tables.shape[1]),
        interpret=_interpret_mode())


# jit, so that the layers of a model share ONE lowering of the kernel:
# its body (the heads are unrolled) takes a second or two to lower on the
# chip's host, and a decode program lowered it once a layer, in every
# process's set-up (30-39 s for 16 layers; my chip runs, PR 25).
@functools.partial(jax.jit, static_argnames=("sm_scale", "pages_per_block",
                                             "interpret"))
def _paged_decode_batch_call(q, k_pool, v_pool, page_tables, lengths, *,
                             sm_scale: float, pages_per_block: int,
                             interpret: bool):
    B, H, D = q.shape
    P, Hkv, page_size, _ = k_pool.shape
    groups = H // Hkv
    table_pages = page_tables.shape[1]
    buf = (2, pages_per_block, Hkv, page_size, D)
    row = pl.BlockSpec((1, Hkv, groups, D), lambda b, ln, pt: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[row,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM(buf, k_pool.dtype),
            pltpu.VMEM(buf, v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),        # (K | V, slot)
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((Hkv, groups, 1), jnp.float32),
            pltpu.VMEM((Hkv, groups, 1), jnp.float32),
            pltpu.VMEM((Hkv, groups, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_batch_kernel, page_size=page_size,
                          pages_per_block=pages_per_block,
                          table_pages=table_pages, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, groups, D), q.dtype),
        # The slot handed from one sequence to the next makes the grid a
        # sequence, not a set.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.minimum(lengths.astype(jnp.int32), table_pages * page_size),
      page_tables.astype(jnp.int32).reshape(-1),
      q.reshape(B, Hkv, groups, D), k_pool, v_pool)
    return out.reshape(B, H, D)


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
        owned = self._owned.get(seq_id, [])
        pad = owned[-1] if owned else 0
        rows = (owned + [pad] * npages)[:npages]
        return jnp.asarray(rows, jnp.int32)

    def free(self, seq_id: str) -> None:
        self._free.extend(reversed(self._owned.pop(seq_id, [])))
