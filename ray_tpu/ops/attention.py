"""Attention kernels: Pallas flash attention + ring attention.

The reference ships NO attention kernels — its compute plane is torch
(SURVEY.md §2.4: sequence/context parallelism "absent in reference"; §5
names Pallas ring/flash attention as the rebuild's native additions).

- `flash_attention`: TPU Pallas kernel, online-softmax forward with the
  canonical (batch, heads, q-block, k-block) grid; k is the innermost
  sequential grid dimension so VMEM scratch accumulators persist across k
  steps. Backward is a blockwise lax.scan recomputation using the saved
  logsumexp (memory O(S·block) not O(S²)).
- `ring_attention`: sequence-parallel attention inside `shard_map` — each
  device holds a sequence shard of Q/K/V; K/V shards rotate around the mesh
  axis via `lax.ppermute` while a running (out, max, denom) merge keeps
  exact softmax semantics. Communication rides ICI and overlaps with the
  per-step flash computation.

On CPU (tests) the Pallas kernel runs in interpret mode.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.util.collective.ops import axis_size as _axis_size

_NEG_INF = -1e30


def _interpret_mode() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Pallas flash attention (forward)
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_scratch, l_scratch, acc_scratch,
                      *, sm_scale: float, causal: bool,
                      block_q: int, block_k: int, num_k_blocks: int,
                      kv_valid_len: int | None = None):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, _NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    # Causal: blocks strictly above the diagonal are fully masked — skip
    # their compute entirely (the index map also clamps their DMAs onto
    # the diagonal block, so skipped steps copy nothing new).  This halves
    # causal attention FLOPs, like the canonical TPU flash kernel.
    needed = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(needed)
    def _compute():
        # Keep q/k/v in their storage dtype (bf16 on TPU): the MXU runs
        # bf16×bf16→f32 at full rate; upcasting inputs to f32 first would
        # halve matmul throughput. Accumulation is f32 via
        # preferred_element_type.
        q = q_ref[0, 0]                                # (block_q, d)
        k = k_ref[0, 0]                                # (block_k, d)
        v = v_ref[0, 0]                                # (block_k, d)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale

        if causal:
            # Only diagonal-straddling blocks need the mask; interior
            # blocks (block fully below diagonal) skip it.
            q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = ki * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        if kv_valid_len is not None and \
                kv_valid_len < num_k_blocks * block_k:
            # Sequence padded up to a block multiple: keys at or beyond
            # kv_valid_len are invisible.  (Static shapes — the mask is an
            # elementwise where; interior blocks pass through unchanged.)
            k_pos = ki * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos < kv_valid_len, s, _NEG_INF)

        m_prev = m_scratch[:]                        # (block_q, 1)
        l_prev = l_scratch[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Guard fully-masked rows (m_new == -inf) against NaNs.
        m_safe = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(jnp.where(s <= _NEG_INF / 2, -jnp.inf, s - m_safe))
        alpha = jnp.exp(jnp.where(m_prev <= _NEG_INF / 2, -jnp.inf,
                                  m_prev - m_safe))
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scratch[:] = acc_scratch[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scratch[:] = m_new
        l_scratch[:] = l_new

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        l = l_scratch[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scratch[:] / l_safe).astype(o_ref.dtype)
        m = m_scratch[:]
        lse = jnp.where(l == 0.0, _NEG_INF, m + jnp.log(l_safe))
        lse_ref[0, 0] = lse.astype(jnp.float32)


def _operand_vma(*arrays) -> frozenset:
    """Union of mesh axes the operands vary over (empty outside shard_map)."""
    vma: frozenset = frozenset()
    for a in arrays:
        vma = vma | jax.typeof(a).vma
    return vma


def _flash_forward(q, k, v, sm_scale: float, causal: bool,
                   block_q: int, block_k: int,
                   kv_valid_len: int | None = None):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]

    def fit_block(block, seq):
        # Largest block ≤ requested that divides the sequence (halving
        # first — stays MXU-aligned for the common power-of-two seqs —
        # then any divisor; a prime length degrades to one block).
        block = min(block, seq)
        while block > 1 and seq % block:
            block //= 2
        if seq % block:
            block = seq
        return block

    block_q = fit_block(block_q, Sq)
    block_k = fit_block(block_k, Sk)
    grid = (B, H, Sq // block_q, Sk // block_k)

    if causal:
        # Clamp skipped (above-diagonal) blocks onto the diagonal: Pallas
        # elides the DMA when the block index repeats, so skipped grid
        # steps move no data.
        def kv_index(b, h, qi, ki):
            last = (qi * block_q + block_q - 1) // block_k
            return (b, h, jnp.minimum(ki, last), 0)
    else:
        def kv_index(b, h, qi, ki):
            return (b, h, ki, 0)

    out, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          num_k_blocks=Sk // block_k,
                          kv_valid_len=kv_valid_len),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), kv_index),
            pl.BlockSpec((1, 1, block_k, D), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=[
            # vma: under shard_map (ring/Ulysses wrappers) outputs vary
            # over the same mesh axes as the operands; required when the
            # kernel is called with check_vma=True (the default).
            jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype,
                                 vma=_operand_vma(q, k, v)),
            jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32,
                                 vma=_operand_vma(q, k, v)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ) if not _interpret_mode() else None,
        interpret=_interpret_mode(),
    )(q, k, v)
    return out, lse.reshape(B, H, Sq)



# ---------------------------------------------------------------------------
# Backward: blockwise recomputation with saved logsumexp
# ---------------------------------------------------------------------------


def _flash_backward(sm_scale, causal, block_q, block_k, kv_valid_len, res, do):
    # Operands stay in their storage dtype (bf16 on TPU — full-rate MXU);
    # every einsum accumulates in f32 via preferred_element_type, and the
    # dk/dv accumulators are f32.
    q, k, v, out, lse = res
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    f32 = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    delta = f32("bhsd,bhsd->bhs", out, do)                   # (B,H,Sq)

    bq = min(block_q, Sq)
    if Sq % bq:
        bq = Sq

    def p_block(qi_start, q_blk, lse_blk):
        s = f32("bhqd,bhkd->bhqk", q_blk, k) * sm_scale
        if causal:
            q_pos = qi_start + jnp.arange(q_blk.shape[2])[:, None]
            k_pos = jnp.arange(Sk)[None, :]
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        if kv_valid_len is not None and kv_valid_len < Sk:
            # Same padded-key mask as the forward: without it the
            # recomputed p would leak gradient into padding keys.
            s = jnp.where(jnp.arange(Sk)[None, :] < kv_valid_len, s,
                          _NEG_INF)
        return jnp.exp(s - lse_blk[..., None])

    def scan_body(carry, idx):
        dk_acc, dv_acc = carry
        qs = idx * bq
        q_blk = lax.dynamic_slice_in_dim(q, qs, bq, axis=2)
        do_blk = lax.dynamic_slice_in_dim(do, qs, bq, axis=2)
        lse_blk = lax.dynamic_slice_in_dim(lse, qs, bq, axis=2)
        dl_blk = lax.dynamic_slice_in_dim(delta, qs, bq, axis=2)
        p = p_block(qs, q_blk, lse_blk)                      # (B,H,bq,Sk) f32
        pb = p.astype(v.dtype)
        dv_acc = dv_acc + f32("bhqk,bhqd->bhkd", pb, do_blk)
        dp = f32("bhqd,bhkd->bhqk", do_blk, v)
        ds = (p * (dp - dl_blk[..., None]) * sm_scale).astype(v.dtype)
        dq_blk = f32("bhqk,bhkd->bhqd", ds, k)
        dk_acc = dk_acc + f32("bhqk,bhqd->bhkd", ds, q_blk)
        return (dk_acc, dv_acc), dq_blk

    init = (jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32))
    vma = tuple(_operand_vma(q, k, v, do))
    if vma:  # under shard_map the carries vary like the operands
        init = tuple(lax.pcast(x, vma, to="varying") for x in init)
    (dk, dv), dq_blocks = lax.scan(scan_body, init, jnp.arange(Sq // bq))
    # dq_blocks: (nq, B, H, bq, D) → (B, H, Sq, D)
    dq = jnp.moveaxis(dq_blocks, 0, 2).reshape(B, H, Sq, D)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, sm_scale: float | None = None,
                    causal: bool = False, block_q: int = 512,
                    block_k: int = 512):
    """Flash attention. q,k,v: (batch, heads, seq, head_dim).

    Default (block_q, block_k) = (512, 512): chosen by IN-MODEL A/B on
    a real v5e chip (1.2B decoder bench, B2 S2048): 249.6-250.1 ms/step
    vs 254.1-254.3 for (1024, 1024), reproducibly — even though the
    standalone kernel sweep (scripts/tpu_kernel_sweep.py) ranks 1024^2
    faster in isolation (7.18 vs 11.16 ms fwd+bwd). Trust end-to-end
    timings over microbenchmarks here; re-sweep in-model if the
    flagship shape changes. Blocks are clamped to the sequence length
    for shorter inputs.
    """
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    out, _ = _flash_forward(q, k, v, scale, causal, block_q, block_k)
    return out


def _fa_fwd(q, k, v, sm_scale, causal, block_q, block_k):
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    out, lse = _flash_forward(q, k, v, scale, causal, block_q, block_k)
    return out, (q, k, v, out, lse)


def _fa_bwd(sm_scale, causal, block_q, block_k, res, do):
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(res[0].shape[-1])
    return _flash_backward(scale, causal, block_q, block_k, None, res, do)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def mha_reference(q, k, v, sm_scale: float | None = None, causal: bool = False):
    """Plain jnp attention for correctness checks."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones(s.shape[-2:], dtype=bool))
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Ring attention (sequence/context parallelism)
# ---------------------------------------------------------------------------


def ring_attention(q, k, v, axis: str = "sp", *, causal: bool = False,
                   sm_scale: float | None = None):
    """Exact attention over a sequence sharded on a mesh axis.

    Call inside shard_map with q,k,v sequence-sharded on `axis`
    (shape per device: (B, H, S/n, D)). K/V rotate n-1 times around the
    ring via ppermute; a running online-softmax merge keeps exactness.
    For causal masking, chunk index determines global positions.
    """
    n = _axis_size(axis)
    my_idx = lax.axis_index(axis)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    B, H, S, D = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    q32 = q.astype(jnp.float32)

    def body(i, carry):
        """Online-softmax accumulation: acc = Σ exp(s−m)·v, l = Σ exp(s−m)."""
        k_cur, v_cur, acc, m_run, l_run = carry
        k_idx = (my_idx - i) % n  # which global chunk we currently hold
        s = jnp.einsum("bhqd,bhkd->bhqk", q32,
                       k_cur.astype(jnp.float32)) * scale
        if causal:
            q_pos = my_idx * S + jnp.arange(S)[:, None]
            k_pos = k_idx * S + jnp.arange(S)[None, :]
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_run, m_cur)
        m_safe = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(jnp.where(s <= _NEG_INF / 2, -jnp.inf, s - m_safe))
        alpha = jnp.exp(jnp.where(m_run <= _NEG_INF / 2, -jnp.inf,
                                  m_run - m_safe))
        acc = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p,
                                       v_cur.astype(jnp.float32))
        l_run = l_run * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # Rotate k/v around the ring (result unused on the last step).
        k_nxt = lax.ppermute(k_cur, axis, perm)
        v_nxt = lax.ppermute(v_cur, axis, perm)
        return k_nxt, v_nxt, acc, m_new, l_run

    # Mark the carries as varying over the ring axis so the scan carry
    # types match (shard_map's varying-axis type system).
    _vary = lambda x: lax.pcast(x, axis, to="varying")  # noqa: E731
    acc0 = _vary(jnp.zeros((B, H, S, D), jnp.float32))
    m0 = _vary(jnp.full((B, H, S, 1), _NEG_INF, jnp.float32))
    l0 = _vary(jnp.zeros((B, H, S, 1), jnp.float32))
    _, _, acc, _, l = lax.fori_loop(0, n, body, (k, v, acc0, m0, l0))
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Ulysses attention (all-to-all sequence parallelism)
# ---------------------------------------------------------------------------


def ulysses_attention(q, k, v, axis: str = "sp", *, causal: bool = False,
                      sm_scale: float | None = None):
    """DeepSpeed-Ulysses-style sequence parallelism inside shard_map.

    Inputs are sequence-sharded on `axis`: per-device (B, H, S/n, D).
    One all-to-all re-shards sequence→heads: (B, H/n, S, D) — each device
    then holds the FULL sequence for H/n heads and runs ordinary (flash)
    attention locally; a second all-to-all restores sequence sharding.
    Two all-to-alls ride ICI vs ring attention's n-1 ppermute hops —
    better when H ≥ n and the sequence fits per-device after head split.

    The reference has no sequence parallelism at all (SURVEY.md §2.4: SP
    "absent", Ulysses named as the rebuild deliverable).
    """
    n = _axis_size(axis)
    B, H, S, D = q.shape  # S = local shard of the sequence
    if H % n:
        raise ValueError(f"ulysses needs heads ({H}) divisible by axis ({n})")

    def seq_to_heads(x):
        # (B, H, S_local, D) -> (B, H/n, S_full, D): head dim scatters
        # across devices, sequence chunks gather in device order.
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                              tiled=True)

    def heads_to_seq(x):
        # (B, H/n, S_full, D) -> (B, H, S_local, D): inverse exchange.
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    if _interpret_mode():
        out = mha_reference(qh, kh, vh, sm_scale, causal)
    else:
        out = flash_attention(qh, kh, vh, sm_scale, causal)
    return heads_to_seq(out.astype(q.dtype))
