"""Paged decode attention: single-query attention over a block-table KV
pool (the vLLM "PagedAttention" idea, TPU-shaped).

The KV cache is a shared POOL of fixed-size pages; each sequence owns a
page table of pool indices. HBM is allocated by total resident tokens,
not `max_len x slots` — the round-1 engine's admitted waste
(reference: the reference serves LLMs through vLLM-style external
engines whose core trick is exactly this block table).

The kernel uses Pallas scalar prefetch (PrefetchScalarGridSpec): the
page table rides in SMEM and the grid's index_map dereferences it, so
each grid step DMAs one page of K/V straight from the pool — attention
runs over scattered pages without ever materializing a contiguous
per-sequence cache. Online softmax accumulates across pages (same
recurrence as ops/attention.py's flash kernel).

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


def _online_softmax_update(pi, length, q, k, v, m_prev, l_prev, acc_prev,
                           *, page_size: int, sm_scale: float):
    """One page of the online-softmax recurrence, shared by EVERY paged
    kernel variant (single-sequence, grid-batched, fused-heads) so a
    numerics change cannot silently miss one of them.

    Pure function of values: callers own the scratch-ref IO (the fused
    kernel updates row SLICES of shared scratch). Every dot is a plain
    2D (G, D) x (page, D) matmul: Mosaic lowers 2D dots onto the MXU
    but rejects the batched `hgd,thd` einsum form ("batch dims must be
    equal" on real TPU; caught by scripts/tpu_kernel_sweep.py on-chip
    validation). Returns (m_new, l_new, acc_new).
    """
    # scores[g, t] = q[g, :] . k[t, :]  — 2D dot, MXU-safe
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ()))) * sm_scale
    token_idx = pi * page_size + jax.lax.broadcasted_iota(
        jnp.int32, scores.shape, 1)
    scores = jnp.where(token_idx < length, scores, _NEG_INF)

    m_cur = jnp.max(scores, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)                 # (G, page)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))  # (G, D)
    return m_new, l_new, acc_prev * alpha + pv


def _normalized(l, acc):
    """Final softmax normalization with the all-masked guard (l == 0)."""
    return acc / jnp.where(l == 0.0, 1.0, l)


def _online_softmax_page_step(pi, num_page_steps, length, q, k, v,
                              o_write, m_scratch, l_scratch, acc_scratch,
                              *, page_size: int, sm_scale: float):
    """One grid step over whole-scratch refs (single-sequence and
    head-on-grid batched kernels). pi: page-step program id; q: (G, D);
    k/v: (page, D); o_write: callback writing the normalized (G, D)
    output on the last step."""
    @pl.when(pi == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, _NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    m_new, l_new, acc_new = _online_softmax_update(
        pi, length, q, k, v, m_scratch[...], l_scratch[...],
        acc_scratch[...], page_size=page_size, sm_scale=sm_scale)
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


def _paged_decode_batch_kernel(page_table_ref, length_ref,  # scalar prefetch
                               q_ref, k_ref, v_ref, o_ref,
                               m_scratch, l_scratch, acc_scratch,
                               *, page_size: int, sm_scale: float):
    # Grid: (B, Hkv, npages); pages iterate fastest, so per-(b, h)
    # scratch resets at pi == 0 and writes back on the last page step.
    b = pl.program_id(0)
    pi = pl.program_id(2)

    def write(out):
        o_ref[0, 0] = out.astype(o_ref.dtype)

    _online_softmax_page_step(
        pi, pl.num_programs(2), length_ref[b],
        q_ref[0, 0].astype(jnp.float32),        # (G, D)
        k_ref[0, 0].astype(jnp.float32),        # (page, D)
        v_ref[0, 0].astype(jnp.float32),
        write, m_scratch, l_scratch, acc_scratch,
        page_size=page_size, sm_scale=sm_scale)


def _paged_decode_batch_fused_kernel(page_table_ref, length_ref,  # prefetch
                                     q_ref, k_ref, v_ref, o_ref,
                                     m_scratch, l_scratch, acc_scratch,
                                     *, page_size: int, num_heads: int,
                                     groups: int, sm_scale: float):
    # Grid: (B, npages) — each step DMAs a FULL pool page (all Hkv heads
    # contiguous in the (P, Hkv, page, D) layout) and unrolls a static
    # per-head loop of 2D dots. Hkv-times fewer grid steps and
    # Hkv-times larger transfers than the head-on-grid variant: this
    # kernel is DMA-bound, so transfer size sets throughput.
    b = pl.program_id(0)
    pi = pl.program_id(1)
    length = length_ref[b]

    @pl.when(pi == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, _NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    for h in range(num_heads):      # static: unrolled at trace time
        rows = slice(h * groups, (h + 1) * groups)
        m_new, l_new, acc_new = _online_softmax_update(
            pi, length,
            q_ref[0, h].astype(jnp.float32),       # (G, D)
            k_ref[0, h].astype(jnp.float32),       # (page, D)
            v_ref[0, h].astype(jnp.float32),
            m_scratch[rows], l_scratch[rows], acc_scratch[rows],
            page_size=page_size, sm_scale=sm_scale)
        m_scratch[rows] = m_new
        l_scratch[rows] = l_new
        acc_scratch[rows] = acc_new

    @pl.when(pi == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = _normalized(l_scratch[...],
                               acc_scratch[...]).astype(o_ref.dtype)


def paged_decode_attention_batch(q, k_pool, v_pool, page_tables, lengths,
                                 *, sm_scale: float | None = None,
                                 fused_heads: bool = False):
    """Batched single-token decode attention over paged KV.

    The batch dimension is a leading GRID axis (not vmap — scalar-prefetch
    pallas calls don't batch), so one compiled program serves every slot
    of a continuous-batching engine per decode step.

    q:           (B, H, D) one query per sequence
    k/v_pool:    (P, Hkv, page_size, D) pools SHARED by all sequences
                 (head-then-page minor layout; see paged_decode_attention)
    page_tables: (B, NP) int32 pool indices per sequence
    lengths:     (B,) int32 valid token counts (incl. current tokens)
    fused_heads: one grid step per (sequence, page) covering ALL KV
                 heads (full-page contiguous DMA, Hkv-times fewer grid
                 steps) vs one per (sequence, head, page). Default stays
                 False until the fused variant passes on-chip Mosaic
                 validation (scripts/tpu_kernel_sweep.py) — interpret
                 mode has accepted kernels real TPU rejects before.
    Returns (B, H, D).
    """
    B, H, D = q.shape
    P, Hkv, page_size, _ = k_pool.shape
    groups = H // Hkv
    npages = page_tables.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)

    q4 = q.reshape(B, Hkv, groups, D)
    if fused_heads:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, npages),
            in_specs=[
                pl.BlockSpec((1, Hkv, groups, D),
                             lambda b, i, pt, ln: (b, 0, 0, 0)),
                pl.BlockSpec((1, Hkv, page_size, D),
                             lambda b, i, pt, ln: (pt[b, i], 0, 0, 0)),
                pl.BlockSpec((1, Hkv, page_size, D),
                             lambda b, i, pt, ln: (pt[b, i], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, Hkv * groups, D),
                                   lambda b, i, pt, ln: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Hkv * groups, 1), jnp.float32),
                pltpu.VMEM((Hkv * groups, 1), jnp.float32),
                pltpu.VMEM((Hkv * groups, D), jnp.float32),
            ],
        )
        out = pl.pallas_call(
            functools.partial(_paged_decode_batch_fused_kernel,
                              page_size=page_size, num_heads=Hkv,
                              groups=groups, sm_scale=sm_scale),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, Hkv * groups, D), q.dtype),
            interpret=_interpret_mode(),
        )(page_tables.astype(jnp.int32), lengths.astype(jnp.int32),
          q4, k_pool, v_pool)
        return out.reshape(B, H, D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, npages),
        in_specs=[
            pl.BlockSpec((1, 1, groups, D),
                         lambda b, h, i, pt, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, D),
                         lambda b, h, i, pt, ln: (pt[b, i], h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, D),
                         lambda b, h, i, pt, ln: (pt[b, i], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, groups, D),
                               lambda b, h, i, pt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((groups, 1), jnp.float32),
            pltpu.VMEM((groups, 1), jnp.float32),
            pltpu.VMEM((groups, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_batch_kernel, page_size=page_size,
                          sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, groups, D), q.dtype),
        interpret=_interpret_mode(),
    )(page_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q4, k_pool, v_pool)
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
