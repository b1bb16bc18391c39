"""Attention kernels: Pallas flash attention + ring attention.

The reference ships NO attention kernels — its compute plane is torch
(SURVEY.md §2.4: sequence/context parallelism "absent in reference"; §5
names Pallas ring/flash attention as the rebuild's native additions).

- `flash_attention`: TPU Pallas kernels. Online-softmax forward with the
  canonical (batch, heads, q-block, k-block) grid; k is the innermost
  sequential grid dimension so VMEM scratch accumulators persist across k
  steps. The backward is one kernel on the grid turned round (q innermost:
  dk and dv sum over q-blocks, dq over k-blocks in a VMEM accumulator of
  the whole query length) that recomputes p from the saved logsumexp, so
  no block of scores ever lies in HBM; causal blocks above the diagonal
  are skipped in both.
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
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.util.collective.ops import axis_size as _axis_size

_NEG_INF = -1e30

# The names `flash_attention`'s forward rule gives the kernel's two results
# (`out`, and the logsumexp the backward recomputes p from).
FLASH_OUT, FLASH_LSE = "flash_out", "flash_lse"


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


def _fit_block(block: int, seq: int) -> int:
    """Largest block ≤ requested that divides the sequence (halving first:
    stays MXU-aligned for the common power-of-two lengths; a length that
    no halving divides degrades to one block)."""
    block = min(block, seq)
    while block > 1 and seq % block:
        block //= 2
    if seq % block:
        block = seq
    return block


def _flash_forward(q, k, v, sm_scale: float, causal: bool,
                   block_q: int, block_k: int,
                   kv_valid_len: int | None = None):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]

    block_q = _fit_block(block_q, Sq)
    block_k = _fit_block(block_k, Sk)
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
# A prompt over itself: the forward kernel alone, for a prefill with no
# earlier keys
# ---------------------------------------------------------------------------


def _causal_over_itself(q_ref, k_ref, v_ref, o_ref, lse_row, *scratch, **kw):
    # The forward kernel as it is; its logsumexp (the backward's residual)
    # lands in VMEM scratch and goes nowhere.
    _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_row, *scratch, **kw)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k"))
def causal_over_itself(q, k, v, block_q: int = 1024, block_k: int = 1024):
    """Causal attention of a sequence over itself, forward only: what a
    prefill over a fresh cache is. q: (B, Hq, S, D); k, v: (B, Hkv, S, D)
    with Hq a multiple of Hkv: query head h reads KV head h // (Hq // Hkv)
    through the index map, so grouped-query K and V are never repeated in
    HBM. No logsumexp is written (nothing differentiates through this),
    and no block of scores leaves VMEM. Blocks are fitted to S as
    `flash_attention`'s are (`_fit_block`: 2304 runs at 256).

    Default (1024, 1024), by in-model A/B on a TPU v5 lite, one chip (PR
    39): the whole prefill program of a 16-layer Mistral-7B (32 / 8 heads
    of 128, bf16), rows x bucket 1 x 1024, 1 x 2048, 4 x 2048, 8 x 1024:
    43.9, 89.0, 391.5, 377.5 ms against 45.2, 91.7, 406.0, 389.8 at
    (512, 512), 2.7-3.6% of the program where attention is an eighth of
    it. There is no backward here to pull the other way, which is what
    kept `flash_attention` at 512.

    Jitted so that a program's layers share ONE lowering of the kernel:
    lowered anew at each of 16 call sites it added 0.7 s to every prefill
    program a replica warms up, 4.5 s of docs-closed's 41-s `setup_s`
    (PR 39; the compiled program is the same, calls are inlined)."""
    B, H, S, D = q.shape
    rep = H // k.shape[1]
    block_q = _fit_block(block_q, S)
    block_k = _fit_block(block_k, S)
    num_k_blocks = S // block_k

    def q_index(b, h, qi, ki):
        return (b, h, qi, 0)

    def kv_index(b, h, qi, ki):
        # blocks above the diagonal are skipped by the kernel; clamped
        # onto the diagonal here they move nothing either
        last = (qi * block_q + block_q - 1) // block_k
        return (b, h // rep, jnp.minimum(ki, last), 0)

    interpret = _interpret_mode()
    return pl.pallas_call(
        functools.partial(_causal_over_itself, sm_scale=1.0 / math.sqrt(D),
                          causal=True, block_q=block_q, block_k=block_k,
                          num_k_blocks=num_k_blocks),
        grid=(B, H, S // block_q, num_k_blocks),
        in_specs=[pl.BlockSpec((1, 1, block_q, D), q_index),
                  pl.BlockSpec((1, 1, block_k, D), kv_index),
                  pl.BlockSpec((1, 1, block_k, D), kv_index)],
        out_specs=pl.BlockSpec((1, 1, block_q, D), q_index),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1, block_q, 1), jnp.float32),   # logsumexp
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Pallas flash attention (backward): one kernel, p recomputed from the saved
# logsumexp, nothing of size Sq x Sk outside VMEM
# ---------------------------------------------------------------------------
#
# With p = exp(s - lse), dp = dO v^T, delta = rowsum(o * dO) and
# ds = p * (dp - delta) * sm_scale:   dv = p^T dO,  dk = ds^T q,  dq = ds k.
# The grid is (batch, heads, k-block, q-block), q innermost: dk and dv of a
# key block sum over the query blocks in VMEM scratch, and dq sums over the
# key blocks in a float32 accumulator that holds the whole query length (1 MB
# at 2048 x 128), so every block of scores is computed once and feeds all
# five matmuls.  Operands go to the MXU in their storage dtype with float32
# accumulation; p and ds are rounded to the storage dtype before their
# matmuls.  Causal: blocks wholly above the diagonal are skipped by the
# forward's rule, and their index maps clamped onto the first block that is
# needed so that a skipped step moves nothing.
#
# Why one kernel and not the usual two (dk/dv summed over q-blocks, dq over
# k-blocks, the scores computed twice): on a TPU v5 lite at (4, 16, 2048, 128)
# bf16 causal, blocks of 512, 1.64 ms against 2.63 ms, results equal bit for
# bit (PR 31).  The price is the accumulator: 4 * Sq * D bytes of VMEM.


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                      *, sm_scale: float, causal: bool,
                      block_q: int, block_k: int):
    """Scores are held transposed, (block_k, block_q): then p^T dO and ds^T q
    are plain matmuls, only dq's contracts over the leading axis, and lse and
    delta come in as lane-dense rows."""
    ki, qi = pl.program_id(2), pl.program_id(3)
    last_k, last_q = pl.num_programs(2) - 1, pl.num_programs(3) - 1

    @pl.when((ki == 0) & (qi == 0))
    def _init_dq():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(qi == 0)
    def _init_dkv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    needed = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0]                                # (block_q, d)
        k = k_ref[0, 0]                                # (block_k, d)
        v = v_ref[0, 0]
        do = do_ref[0, 0]                              # (block_q, d)
        f32 = functools.partial(lax.dot_general,
                                preferred_element_type=jnp.float32)
        a_bt = (((1,), (1,)), ((), ()))
        a_b = (((1,), (0,)), ((), ()))
        at_b = (((0,), (0,)), ((), ()))
        s_t = f32(k, q, a_bt) * sm_scale               # (block_k, block_q)
        if causal:
            k_off = lax.broadcasted_iota(jnp.int32, s_t.shape, 0)
            q_off = lax.broadcasted_iota(jnp.int32, s_t.shape, 1)
            s_t = jnp.where(q_off - k_off >= ki * block_k - qi * block_q,
                            s_t, _NEG_INF)
        p_t = jnp.exp(s_t - lse_ref[0, 0, 0])
        dv_acc[:] += f32(p_t.astype(do.dtype), do, a_b)
        dp_t = f32(v, do, a_bt)
        ds_t = (p_t * (dp_t - delta_ref[0, 0, 0]) * sm_scale).astype(q.dtype)
        dk_acc[:] += f32(ds_t, q, a_b)
        rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
        dq_acc[rows, :] += f32(ds_t, k, at_b)

    @pl.when(qi == last_q)
    def _finish_dkv():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when((ki == last_k) & (qi == last_q))
    def _finish_dq():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


# One jitted function, so that a model's layers share one lowering of the
# kernel body (a body lowered once a layer cost +50% set-up; PR 25).
@functools.partial(jax.jit, static_argnames=("sm_scale", "causal", "block_q",
                                             "block_k", "interpret"))
def _flash_backward(q, k, v, out, lse, do, *, sm_scale: float, causal: bool,
                    block_q: int, block_k: int, interpret: bool):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    block_q = _fit_block(block_q, Sq)
    block_k = _fit_block(block_k, Sk)
    nq = Sq // block_q
    delta = jnp.einsum("bhsd,bhsd->bhs", out, do,
                       preferred_element_type=jnp.float32)

    def q_index(b, h, ki, qi):
        if causal:  # a query block wholly before the key block is skipped
            qi = jnp.maximum(qi, (ki * block_k) // block_q)
        return (b, h, qi, 0)

    q_rows = pl.BlockSpec((1, 1, block_q, D), q_index)
    q_stat = pl.BlockSpec((1, 1, 1, 1, block_q),
                          lambda b, h, ki, qi: q_index(b, h, ki, qi) + (0,))
    k_rows = pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, 0))
    q_whole = pl.BlockSpec((1, 1, Sq, D), lambda b, h, ki, qi: (b, h, 0, 0))
    as_rows = lambda x: x.reshape(B, H, nq, 1, block_q)  # noqa: E731
    vma = _operand_vma(q, k, v, do)
    # What stays in VMEM for a whole (batch, head): dq's accumulator and its
    # output block, twice (the pipeline's two buffers), beside the blocks,
    # which have fitted the compiler's default limit of 16 MiB so far.
    resident = Sq * D * (4 + 2 * q.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_flash_bwd_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(B, H, Sk // block_k, nq),
        in_specs=[q_rows, k_rows, k_rows, q_rows, q_stat, q_stat],
        out_specs=[q_whole, k_rows, k_rows],
        out_shape=tuple(jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma)
                        for x in (q, k, v)),
        scratch_shapes=[pltpu.VMEM((Sq, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=resident + 16 * 1024 * 1024),
        interpret=interpret,
    )(q, k, v, do, as_rows(lse), as_rows(delta))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, sm_scale: float | None = None,
                    causal: bool = False, block_q: int = 512,
                    block_k: int = 512):
    """Flash attention. q,k,v: (batch, heads, seq, head_dim).

    `block_q` and `block_k` are the blocks of the forward and of the
    backward kernel alike, each fitted to its sequence (`_fit_block`).
    Default (512, 512), by in-model A/B on a TPU v5 lite, one chip (PR 31):
    the train step of `internlm2-train-packed2k` (InternLM2-1.8B whole, 24
    layers, remat "full", q/k/v bf16 (4, 16, 2048, 128), causal), forward at
    (512, 512), median of 8 steps with the backward at (512, 512) 767.5 ms,
    (1024, 1024) 767.4, (1024, 512) 769.1, (512, 1024) 770.0; the backward
    alone at those shapes 1.64, 1.70, 1.76, 1.76 ms, and 2.08 / 1.99 ms at
    (256, 512) / (512, 256). So the backward takes the caller's blocks and
    has none of its own. (The forward alone ranks (1024, 1024) first, 1.34
    against 1.84 ms; an earlier in-model A/B on a 1.2B decoder had the step
    2% slower with it. Trust end-to-end timings over the kernel alone, and
    re-run the A/B in the model if the flagship shape changes.)

    Under differentiation the forward's two results are named `FLASH_OUT`
    and `FLASH_LSE` (`jax.ad_checkpoint.checkpoint_name`): a caller that
    rematerialises its layers keeps them with `save_only_these_names`
    (`models/llama.py`, remat "full"), and the backward then finds its
    residuals without the forward kernel running a second time. That call
    is the dearest thing a layer can run twice: 1.34 ms at the shape above,
    a quarter of its compute floor. Outside a `jax.checkpoint` a name is the
    identity and every program lowers as it did without it.
    """
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    out, _ = _flash_forward(q, k, v, scale, causal, block_q, block_k)
    return out


def _fa_fwd(q, k, v, sm_scale, causal, block_q, block_k):
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    out, lse = _flash_forward(q, k, v, scale, causal, block_q, block_k)
    # For a caller's `jax.checkpoint` policy (see `flash_attention`).
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, out, lse)


def _fa_bwd(sm_scale, causal, block_q, block_k, res, do):
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(res[0].shape[-1])
    return _flash_backward(*res, do, sm_scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k,
                           interpret=_interpret_mode())


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
