"""MiniCPM-SALA decoder (`minicpm_sala`), TPU-first: block-sparse attention
layers (`minicpm4`: InfLLM-V2) among lightning linear-attention layers
(`lightning-attn`), every mixer followed by a gated feed-forward, muP scalings.

    h = scale_emb * E[token]
    for each layer, by `mixer_types`:
        h += r * Mixer(RMSNorm(h))         r = scale_depth / sqrt(depth_layers)
        h += r * W2 (silu(a) * b),  (a, b) = split(W13 RMSNorm(h))
    logits = (RMSNorm(h) / (d_model / dim_model_base)) W_head

`minicpm4`: `[q | k | v | g] = W_in x`, 32 query heads over 2 K/V heads of
128, q and k RMS-normed a head, NO rotary term; out = W_o (sigmoid(g) *
attention).  A sequence of at most `dense_len` tokens attends causally over
all of itself.  Past it every query attends over the tokens of the BLOCKS
(of `block_size` tokens: the engine's pages) it keeps: keys are mean-pooled
in windows of `kernel_size` at `kernel_stride` into compressed keys; the
query's softmax over the compressed keys it can see (a window that lies
wholly at or before it), summed over the heads that share a K/V head and
max-pooled onto every block a window overlaps, ranks the blocks; the
`init_blocks` first, the `window_size / block_size` last (the query's own
among them) and the `topk` best of the others are kept, for each K/V head
its own.  A prompt longer than `dense_len` is sparse at EVERY position (a
query with fewer blocks than are to be kept keeps all of them); a decode
step is sparse once its sequence, this token counted, is longer.

`lightning-attn`: `[q | k | v | g] = W_in x`, 32 heads of 128 each, q and k
RMS-normed and rotated (halves), and for head i with decay lambda_i =
exp(-2^(-8 i / H)), i = 1..H:

    S_t = lambda_i S_{t-1} + v_t (x) k_t        o_t = S_t q_t / sqrt(128)

no softmax and no normaliser; out = W_o (sigmoid(g) * RMSNorm_head(o)).
That is `granite_hybrid.ssd_scan`'s recurrence with a step size of 1, a
constant decay a head, B = k, C = q, x = v, a head its own B and C, and its
one-token form `granite_hybrid.state_step`: both are shared, not copied.

What a served sequence holds (`prefill` returns it, `decode` advances it,
`serve/llm_families.MiniCpmSalaServing` tells the engine): a sparse layer,
pages of K and V, one pool row a (page, K/V head) so that a decode step
reads, for each K/V head, the pages of a table GATHERED from the step's
selection (`ops/paged_attention.paged_decode_attention_batch` unchanged: a
row of its batch is a (sequence, K/V head), its 16 query heads the matrix
unit's rows; in the dense regime the gathered table is the sequence's own);
a second pool of compressed keys (`block_size / kernel_stride` a page and
head) and, fixed per slot, the sums of the two key segments still open (a
compressed key is written when its window completes); a lightning layer,
the state S (H, 128, 128) float32, fixed per slot.

A prompt runs in blocks of `row_block` tokens (a `lax.scan`: the feed-forward
and the projections of 32,768 tokens at once would not fit beside the
weights), a lightning layer carrying S from block to block.  A prompt past
`dense_len` is attended in its MASKED dense form, `query_block` queries at a
time against every key with the blocks a query did not keep masked out: the
sparse result, at the dense form's cost.

Precision: parameters and the K and V of the pages are `dtype`; the stream,
norms, softmaxes (the selection's too), the decay, the scan and S are
float32, and so is everything a block is RANKED by: the compressed keys are
means of the keys before they are rounded for the pages, kept in a float32
pool, and their product with the queries runs at the highest precision.  An
activation enters every product with a weight as two bfloat16 terms
(`models/sambay.matmul`), over a prompt as in a decode step, and the masked
form takes its queries and softmax weights so too: a selection's 64th and
65th block score 0.3% apart, a bfloat16 rounding upstream (0.2%) turns one
into the other at every other selection, and with seeded weights the block
that falls out is as heavy as any (`benchmarks/README-minicpm-sala.md`).

Scopes: `select`, `sparse_attn`, `lightning`, `gate`, `mlp`, `head`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.granite_hybrid import ssd_scan, state_step
from ray_tpu.models.sambay import masked_attention, matmul

_NEG_INF = -1e30
_CAUSAL_PARTS = 4       # of a long prompt's queries, each with its own keys
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
# What a decode step counts, in this order (`MiniCpmSalaModel.decode`).
STEP_COUNTS = ("sparse_pages_read", "sparse_pages_resident", "sparse_rows",
               "compressed_keys_read")


@dataclasses.dataclass(frozen=True)
class MiniCpmSalaConfig:
    vocab_size: int = 73448
    d_model: int = 4096
    mixer_types: tuple = (SPARSE,) + (LIGHTNING,) * 6 + (SPARSE,)
    n_heads: int = 32           # query heads; a lightning layer's q, k and v
    n_kv_heads: int = 2         # of a sparse layer
    head_dim: int = 128
    d_ff: int = 16384
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    depth_layers: int = 32      # the PUBLISHED depth, whatever is held
    dim_model_base: int = 256
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    window_size: int = 2048
    init_blocks: int = 1
    dense_len: int = 8192
    chunk: int = 256            # of the scan's matrix form
    row_block: int = 2048       # tokens of a prompt in flight
    query_block: int = 128      # queries of the masked form in flight
    dtype: Any = jnp.bfloat16
    # a prompt of at most dense_len: "flash" (pallas) or "reference" (jnp)
    attention: str = "flash"

    def __post_init__(self):
        if self.kernel_size != 2 * self.kernel_stride or \
                self.block_size % self.kernel_stride or \
                self.window_size % self.block_size or \
                self.dense_len % self.block_size:
            raise ValueError(
                "compressed keys: windows of two strides, whole strides a "
                "block; whole blocks in the window and in dense_len")
        if self.dense_len // self.block_size < self.kept_blocks:
            raise ValueError(
                f"a sequence past dense_len={self.dense_len} must hold the "
                f"{self.kept_blocks} blocks a query keeps")
        if set(self.mixer_types) - {SPARSE, LIGHTNING} or \
                self.n_heads % self.n_kv_heads:
            raise ValueError(f"mixer_types names {SPARSE!r} or "
                             f"{LIGHTNING!r}; whole groups of query heads")

    @property
    def n_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def residual(self) -> float:
        return self.scale_depth / math.sqrt(self.depth_layers)

    @property
    def window_blocks(self) -> int:
        return self.window_size // self.block_size

    @property
    def kept_blocks(self) -> int:
        return self.init_blocks + self.window_blocks + self.topk

    @property
    def gathered_pages(self) -> int:
        """Columns of a decode step's gathered table: every page of the
        dense regime, or the kept blocks of the sparse one."""
        return max(self.dense_len // self.block_size, self.kept_blocks)

    @property
    def keys_per_block(self) -> int:
        """Compressed keys whose window STARTS in a block."""
        return self.block_size // self.kernel_stride

    @property
    def ckey_row(self) -> int:
        """Values of a page's row in the compressed-key pool."""
        return self.n_kv_heads * self.keys_per_block * self.head_dim

    def layers_of(self, kind: str) -> list:
        return [i for i, k in enumerate(self.mixer_types) if k == kind]

    def decays(self):
        """log lambda_i, i = 1..H: Lightning Attention-2's slopes."""
        H = self.n_heads
        return -(2.0 ** (-8.0 * jnp.arange(1, H + 1, dtype=jnp.float32) / H))


MINICPM_SALA_L8 = MiniCpmSalaConfig()
TINY_SALA = MiniCpmSalaConfig(
    vocab_size=256, d_model=64, mixer_types=(SPARSE, LIGHTNING, LIGHTNING,
                                             SPARSE),
    n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, depth_layers=16,
    dim_model_base=16, kernel_size=4, kernel_stride=2, block_size=8, topk=2,
    window_size=16, init_blocks=1, dense_len=64, chunk=8, row_block=32,
    query_block=16, dtype=jnp.float32, attention="reference")


# ---------------------------------------------------------------------------
# Pure pieces (a prompt's blocks run inside `lax.scan`: no module is called
# there, only these, over the layer's arrays)
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps: float):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(
        jnp.mean(xf * xf, axis=-1, keepdims=True) + eps) * scale


def rotate_halves(x, positions, theta: float):
    """x (..., H, D) float32, positions (...) -> the rotary term over the
    pairs (d, d + D/2), the angles computed from the positions (no table:
    524,288 positions of one would be 268 MB)."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = positions[..., None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def mlp(x, p, c: MiniCpmSalaConfig):
    """(Every product with a weight here takes its activation as two
    bfloat16 terms, `matmul(..., True)`, over a prompt as in a decode
    step.)"""
    with jax.named_scope("mlp"):
        a, b = jnp.split(matmul(rms_norm(x, p["post_norm"], c.norm_eps),
                                p["w13"], True), 2, axis=-1)
        return x + c.residual * matmul(nn.silu(a) * b, p["w2"], True)


def _gated_out(x, o, g, p, c: MiniCpmSalaConfig):
    """x + r * W_o (sigmoid(g) * o): o, g (..., H * D) float32."""
    with jax.named_scope("gate"):
        return x + c.residual * matmul(jax.nn.sigmoid(g) * o, p["o_proj"],
                                       True)


# ---- the sparse layers -----------------------------------------------------


def sparse_project(x, p, c: MiniCpmSalaConfig):
    """x (..., d) -> q (..., Hq, D) and k (..., Hkv, D) normed a head,
    float32; v (..., Hkv, D); g (..., Hq * D)."""
    Hq, Hkv, D = c.n_heads, c.n_kv_heads, c.head_dim
    q, k, v, g = jnp.split(
        matmul(rms_norm(x, p["input_norm"], c.norm_eps), p["in_proj"], True),
        [Hq * D, (Hq + Hkv) * D, (Hq + 2 * Hkv) * D], axis=-1)
    heads = lambda a, n: a.reshape(*a.shape[:-1], n, D)  # noqa: E731
    return (rms_norm(heads(q, Hq), p["q_norm"], c.norm_eps),
            rms_norm(heads(k, Hkv), p["k_norm"], c.norm_eps),
            heads(v, Hkv), g)


def segment_sums(k, c: MiniCpmSalaConfig):
    """k (B, Hkv, S, D) float32, NOT rounded to the pages' type -> the sums
    of its whole strides, (B, Hkv, S / stride, D): a compressed key is two
    neighbours' mean."""
    B, H, S, D = k.shape
    return k.reshape(B, H, S // c.kernel_stride, c.kernel_stride, D).sum(3)


def compressed_keys(seg, c: MiniCpmSalaConfig):
    """Segment sums (B, Hkv, n, D) -> key j = mean of segments j and j + 1,
    float32, (B, Hkv, n, D): the last has no right neighbour and is never
    visible."""
    both = seg + jnp.pad(seg[:, :, 1:], ((0, 0), (0, 0), (0, 1), (0, 0)))
    return both / c.kernel_size


def rank_blocks(q, ck, qpos, c: MiniCpmSalaConfig):
    """The selection's scores.  q (B, Hkv, G, T, D) float32 normed queries
    at positions qpos (B, T); ck (B, Hkv, J, D) float32 compressed keys,
    key j of the tokens [stride j, stride j + kernel), the product at the
    highest precision.  Returns (cand (B, Hkv, T,
    nb) float32: a block's score where it competes for the top-k, -1
    elsewhere; forced (B, 1, T, nb) bool: kept whatever its score)."""
    with jax.named_scope("select"):
        B, Hkv, G, T, D = q.shape
        J = ck.shape[2]
        n, nb = c.keys_per_block, J // c.keys_per_block
        s = jnp.einsum("bhgtd,bhjd->bhgtj", q, ck,
                       precision=jax.lax.Precision.HIGHEST)
        seen = (c.kernel_stride * jnp.arange(J) + c.kernel_size - 1)[
            None, None, :] <= qpos[:, :, None]                # (B, T, J)
        seen = seen[:, None, None]
        w = jax.nn.softmax(
            jnp.where(seen, s / math.sqrt(D), _NEG_INF), axis=-1)
        w = jnp.sum(jnp.where(seen, w, 0.0), axis=2)          # (B, Hkv, T, J)
        w = w.reshape(B, Hkv, T, nb, n)
        # a block's own windows, and the one that spills in from the left
        spill = jnp.pad(w[..., :-1, n - 1], ((0, 0),) * 3 + ((1, 0),))
        score = jnp.maximum(jnp.max(w, axis=-1), spill)
        blk = jnp.arange(nb)[None, None, :]
        own = (qpos // c.block_size)[:, :, None]              # (B, T, 1)
        forced = (blk < c.init_blocks) | \
            ((blk > own - c.window_blocks) & (blk <= own))
        competes = (blk >= c.init_blocks) & (blk <= own - c.window_blocks)
        return jnp.where(competes[:, None], score, -1.0), forced[:, None]


def best_of(cand, k: int):
    """(..., n) bool: the k highest of each row of `cand` (scores >= 0
    where a block competes, -1 elsewhere), equal scores by lower index, as
    `lax.top_k` orders them; fewer where fewer compete.  The k-th score is
    found by bisection on the scores' bits (non-negative floats order as
    their bits do): 32 comparisons a row, where a sort of every row was a
    tenth of a long prompt's prefill (my chip run, PR 49)."""
    bits = jax.lax.bitcast_convert_type(jnp.maximum(cand, 0.0), jnp.uint32)
    reach = lambda t: jnp.sum(  # noqa: E731
        bits >= t[..., None], axis=-1) >= k

    def halve(_, lo_hi):        # the largest t with k scores at or over it
        lo, hi = lo_hi
        mid = lo + (hi - lo + 1) // 2
        ok = reach(mid)
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    zero = jnp.zeros(cand.shape[:-1], jnp.uint32)
    kth, _ = jax.lax.fori_loop(0, 32, halve,
                               (zero, zero + jnp.uint32(0x7F800000)))
    over = bits > kth[..., None]
    level = bits == kth[..., None]
    room = k - jnp.sum(over, axis=-1, keepdims=True)
    return (over | (level & (jnp.cumsum(level, axis=-1) <= room))) \
        & (cand >= 0.0)


def kept_blocks(q, ck, qpos, c: MiniCpmSalaConfig):
    """(B, Hkv, T, nb) bool: the blocks each query keeps."""
    cand, forced = rank_blocks(q, ck, qpos, c)
    return best_of(cand, c.topk) | forced


def masked_sparse_attention(q, k, v, ck, qpos, sparse_row,
                            c: MiniCpmSalaConfig):
    """q (B, Hkv, G, T, D) float32 at positions qpos (B, T) against k, v
    (B, Hkv, S, D): causal, and where `sparse_row` (B,) over the kept
    blocks only -> (B, Hkv * G, T, D) float32."""
    B, Hkv, G, T, D = q.shape
    S = k.shape[2]
    kept = kept_blocks(q, ck, qpos, c) | ~sparse_row[:, None, None, None]
    with jax.named_scope("sparse_attn"):
        seen = jnp.repeat(kept, c.block_size, axis=-1)[..., :S] \
            & (jnp.arange(S)[None, None, None, :] <= qpos[:, None, :, None])
        return masked_attention(q.reshape(B, Hkv * G, T, D), k, v,
                                seen[:, :, None], 1.0 / math.sqrt(D), True)


def _ckey_pages(ck, c: MiniCpmSalaConfig):
    """(B, Hkv, J, D) -> the compressed-key pool's rows, (B, J / n, Hkv n
    D): a page's keys, head by head."""
    B, Hkv, J, D = ck.shape
    n = c.keys_per_block
    return ck.reshape(B, Hkv, J // n, n, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, J // n, c.ckey_row)


def _ckeys_of(rows, c: MiniCpmSalaConfig):
    """`_ckey_pages` back: (B, NP, row) -> (B, Hkv, NP n, D)."""
    B, NP, _ = rows.shape
    n = c.keys_per_block
    return rows.reshape(B, NP, c.n_kv_heads, n, c.head_dim) \
        .transpose(0, 2, 1, 3, 4).reshape(B, c.n_kv_heads, NP * n,
                                          c.head_dim)


def _in_blocks(f, carry, x, block: int, last_idx, start: int = 0):
    """`f(carry, x_block, positions) -> (carry, y_block)` over blocks of
    `block` positions of x (B, S, ...), one at a time; ys (B, S, ...).
    `positions` (B, block) are the block's own, from `start`; where S is no
    whole blocks the last one runs past S (zeros, at positions no row
    holds).  A block that lies past every row's `last_idx` (B,) is not
    computed: its ys are zeros and the carry passes it by (a prompt fills
    two thirds of its bucket on average)."""
    B, S = jax.tree_util.tree_leaves(x)[0].shape[:2]
    at = lambda first, n: jnp.broadcast_to(  # noqa: E731
        start + first + jnp.arange(n)[None], (B, n))
    if S <= block:
        return f(carry, x, at(0, S))
    n = -(-S // block)

    def where_live(carry, xs):
        xb, pos = xs[0], at(xs[1], block)
        shapes = jax.eval_shape(f, carry, xb, pos)[1]
        return jax.lax.cond(
            jnp.any(pos[:, 0] <= last_idx),
            lambda: f(carry, xb, pos),
            lambda: (carry, jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype), shapes)))

    def cut(a):
        a = jnp.pad(a, ((0, 0), (0, n * block - S))
                    + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape(B, n, block, *a.shape[2:]), 1, 0)

    carry, ys = jax.lax.scan(
        where_live, carry,
        (jax.tree_util.tree_map(cut, x), block * jnp.arange(n)))
    join = lambda a: jnp.moveaxis(a, 0, 1).reshape(  # noqa: E731
        B, n * block, *a.shape[3:])[:, :S]
    return carry, jax.tree_util.tree_map(join, ys)


def sparse_rows(x, p, last_idx, c: MiniCpmSalaConfig):
    """A sparse layer and its feed-forward over whole rows x (B, S, d) ->
    (x, (k, v) (B, Hkv, S, D) as stored, the compressed-key pool's rows
    (B, S / block, row), the open segments' sums at each row's `last_idx`
    (B, Hkv, 2, D) float32)."""
    B, S, _ = x.shape
    Hkv, D, G = c.n_kv_heads, c.head_dim, c.n_heads // c.n_kv_heads

    def project(_, xb, pos):
        q, k, v, g = sparse_project(xb, p, c)
        # (keys past a row's last token are zeros: they are in no sum)
        k = jnp.where((pos <= last_idx[:, None])[..., None, None], k, 0.0)
        return None, (q, k, v.astype(c.dtype), g)

    _, (q, k, v, g) = _in_blocks(project, None, x, c.row_block, last_idx)
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    pad = -S % c.block_size
    seg = segment_sums(jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))), c)
    ck = compressed_keys(seg, c)
    k = k.astype(c.dtype)
    # the two segments still open after `last_idx`: the one the next token
    # falls in (what it holds so far) and the whole one before it
    cur = (last_idx + 1) // c.kernel_stride
    at = jnp.stack([cur - 1, cur], axis=1)                    # (B, 2)
    open_ = jnp.take_along_axis(
        jnp.pad(seg, ((0, 0), (0, 0), (1, 1), (0, 0))),
        (at + 1)[:, None, :, None], axis=2)
    sparse_row = last_idx + 1 > c.dense_len
    grouped = lambda a: a.reshape(  # noqa: E731
        B, a.shape[1], Hkv, G, D).transpose(0, 2, 3, 1, 4)
    if S <= c.dense_len:
        with jax.named_scope("sparse_attn"):
            o = _dense_attention(q, k, v, c)
    else:
        # a quarter of the queries at a time, against the keys up to that
        # quarter's end: three eighths of the masked form's products are
        # above the diagonal and not made
        parts = sorted({i * S // _CAUSAL_PARTS // c.block_size
                        * c.block_size for i in range(_CAUSAL_PARTS)} | {S})
        o = []
        for lo, hi in zip(parts, parts[1:]):
            n = -(-hi // c.block_size) * c.keys_per_block

            def attend(_, qb, pos):
                out = masked_sparse_attention(
                    grouped(qb), k[:, :, :hi], v[:, :, :hi], ck[:, :, :n],
                    pos, sparse_row, c)
                return None, out.transpose(0, 2, 1, 3)
            o.append(_in_blocks(attend, None, q[:, lo:hi], c.query_block,
                                last_idx, lo)[1])
        o = jnp.concatenate(o, axis=1)

    def finish(_, xs, pos):
        xb, ob, gb = xs
        return None, mlp(_gated_out(xb, ob.reshape(*gb.shape), gb, p, c),
                         p, c)

    _, x = _in_blocks(finish, None, (x, o, g), c.row_block, last_idx)
    return x, (k, v), _ckey_pages(ck, c), open_


def _dense_attention(q, k, v, c: MiniCpmSalaConfig):
    """q (B, S, Hq, D) float32; k, v (B, Hkv, S, D) -> (B, S, Hq, D)."""
    q = q.transpose(0, 2, 1, 3)
    if c.attention == "flash":
        from ray_tpu.ops.attention import causal_over_itself

        o = causal_over_itself(q.astype(k.dtype), k, v).astype(jnp.float32)
    else:
        S = q.shape[2]
        o = masked_attention(
            q, k, v, jnp.arange(S)[:, None] >= jnp.arange(S)[None, :],
            1.0 / math.sqrt(c.head_dim))
    return o.transpose(0, 2, 1, 3)


# ---- the lightning layers --------------------------------------------------


def lightning_project(x, p, positions, c: MiniCpmSalaConfig):
    """x (..., d) at `positions` (...) -> q (scaled), k, v (..., H, D)
    float32 and g (..., H * D)."""
    H, D = c.n_heads, c.head_dim
    q, k, v, g = jnp.split(
        matmul(rms_norm(x, p["input_norm"], c.norm_eps), p["in_proj"], True),
        4, axis=-1)
    heads = lambda a: a.reshape(*a.shape[:-1], H, D)  # noqa: E731
    q = rotate_halves(rms_norm(heads(q), p["q_norm"], c.norm_eps),
                      positions, c.rope_theta)
    k = rotate_halves(rms_norm(heads(k), p["k_norm"], c.norm_eps),
                      positions, c.rope_theta)
    return q / math.sqrt(D), k, heads(v), g


def lightning_rows(x, p, last_idx, c: MiniCpmSalaConfig):
    """A lightning layer and its feed-forward over whole rows x (B, S, d)
    -> (x, S (B, H, D, D) float32 after each row's `last_idx`)."""
    B = x.shape[0]

    def block(s, xb, pos):
        q, k, v, g = lightning_project(xb, p, pos, c)
        with jax.named_scope("lightning"):
            # a position past a row's last token leaves the state alone
            dt = jnp.broadcast_to(
                (pos <= last_idx[:, None]).astype(jnp.float32)[..., None],
                (*pos.shape, c.n_heads))
            y, s = ssd_scan(v, dt, c.decays(), k, q, s, chunk=c.chunk)
            y = rms_norm(y, p["out_norm"], c.norm_eps)
        return s, mlp(_gated_out(xb, y.reshape(*g.shape), g, p, c), p, c)

    s0 = jnp.zeros((B, c.n_heads, c.head_dim, c.head_dim), jnp.float32)
    s, x = _in_blocks(block, s0, x, c.row_block, last_idx)
    return x, s


# ---------------------------------------------------------------------------
# The modules: parameters, and the stack
# ---------------------------------------------------------------------------


class Layer(nn.Module):
    """A block's arrays, by name (`weights`): the pure functions above
    take them."""
    cfg: MiniCpmSalaConfig
    kind: str

    def setup(self):
        c = self.cfg
        d, HD = c.d_model, c.n_heads * c.head_dim
        width = 2 * HD + 2 * c.n_kv_heads * c.head_dim \
            if self.kind == SPARSE else 4 * HD
        drawn = nn.initializers.normal(0.02)
        self.p = {
            **{name: self.param(name, nn.initializers.ones, (n,),
                                jnp.float32)
               for name, n in (("input_norm", d), ("post_norm", d),
                               ("q_norm", c.head_dim), ("k_norm", c.head_dim))
               + ((("out_norm", c.head_dim),)
                  if self.kind == LIGHTNING else ())},
            **{name: self.param(name, drawn, shape, c.dtype)
               for name, shape in (("in_proj", (d, width)),
                                   ("o_proj", (HD, d)),
                                   ("w13", (d, 2 * c.d_ff)),
                                   ("w2", (c.d_ff, d)))}}


class MiniCpmSalaModel(nn.Module):
    cfg: MiniCpmSalaConfig

    def setup(self):
        c = self.cfg
        self.embed = self.param("embed", nn.initializers.normal(0.02),
                                (c.vocab_size, c.d_model), c.dtype)
        self.layers = [Layer(c, kind) for kind in c.mixer_types]
        self.norm = self.param("norm", nn.initializers.ones, (c.d_model,),
                               jnp.float32)
        self.lm_head = self.param("lm_head", nn.initializers.normal(0.02),
                                  (c.d_model, c.vocab_size), c.dtype)

    def _embed(self, tokens):
        return self.embed[tokens].astype(jnp.float32) * self.cfg.scale_emb

    def _head(self, x):
        c = self.cfg
        with jax.named_scope("head"):
            return matmul(rms_norm(x, self.norm, c.norm_eps)
                          / (c.d_model / c.dim_model_base), self.lm_head,
                          True)

    def _rows(self, tokens, last_idx):
        c = self.cfg
        x = self._embed(tokens)
        state = {"kv": [], "ckeys": [], "open": [], "lightning": []}
        for layer in self.layers:
            if layer.kind == LIGHTNING:
                x, s = lightning_rows(x, layer.p, last_idx, c)
                state["lightning"].append(s)
                continue
            x, kv, ck, open_ = sparse_rows(x, layer.p, last_idx, c)
            state["kv"].append(kv)
            state["ckeys"].append(ck)
            state["open"].append(open_)
        return x, state

    def __call__(self, tokens):
        """Whole forward: (B, S) -> float32 logits (B, S, V), each row a
        prompt of S tokens (sparse at every position where S is past
        `dense_len`)."""
        B, S = tokens.shape
        x, _ = self._rows(tokens, jnp.full((B,), S - 1, jnp.int32))
        return self._head(x)

    def prefill(self, tokens, last_idx):
        """Right-padded rows (B, S), each row's last token at `last_idx`
        -> float32 logits (B, V) at that token, and the state a decode
        continues from: {"kv": [(k, v) (B, Hkv, S, D)], "ckeys": [(B, S /
        block, row)], "open": [(B, Hkv, 2, D)] a sparse layer; "lightning":
        [S (B, H, D, D)] a lightning layer, AT the row's last token}; and
        what it counted: the rows past `dense_len`."""
        x, state = self._rows(tokens, last_idx)
        last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
        return self._head(last), state, jnp.sum(
            last_idx + 1 > self.cfg.dense_len).astype(jnp.int32)[None]

    def decode(self, token, pos, state, table, length, live):
        """One token a sequence: token (B,) at `pos` (B,), `length` (B,)
        tokens already cached -> float32 logits (B, V), the state with this
        token in it, and STEP_COUNTS over the rows that are `live` (B,).
        state: {"pools": [(k, v) (P Hkv, 1, page, D)], "cpools": [(P,
        row)] under `table` (B, NP); "open", "lightning" as `prefill`
        gives them, batch first}.  A row that is not live keeps what is
        fixed per slot (its pool writes land where its next live step
        writes again)."""
        from ray_tpu.ops.paged_attention import paged_decode_attention_batch

        c = self.cfg
        B, NP = table.shape
        Hkv, D, G = c.n_kv_heads, c.head_dim, c.n_heads // c.n_kv_heads
        n, GW = c.keys_per_block, c.gathered_pages
        x = self._embed(token)                               # (B, d)
        total = length + 1                  # this token counted
        sparse = total > c.dense_len
        own = length // c.block_size        # this token's block
        # -- the gathered table's blocks, but for the top-k ------------------
        window = own[:, None] - c.window_blocks + 1 \
            + jnp.arange(c.window_blocks)[None]
        dense_blocks = jnp.minimum(jnp.arange(GW), NP - 1)
        sparse_len = (c.kept_blocks - 1) * c.block_size \
            + length % c.block_size + 1
        heads = jnp.arange(Hkv)[None, :, None]
        new = {"pools": [], "cpools": [], "open": [], "lightning": []}
        for layer in self.layers:
            p = layer.p
            if layer.kind == LIGHTNING:
                s_prev = state["lightning"][len(new["lightning"])]
                q, k, v, g = lightning_project(x, p, pos, c)
                with jax.named_scope("lightning"):
                    y, s = state_step(s_prev, jnp.exp(c.decays()), v, k, q)
                    y = rms_norm(y, p["out_norm"], c.norm_eps)
                    s = jnp.where(live[:, None, None, None], s, s_prev)
                new["lightning"].append(s)
                x = mlp(_gated_out(x, y.reshape(B, -1), g, p, c), p, c)
                continue
            i = len(new["pools"])
            (k_pool, v_pool), cpool = state["pools"][i], state["cpools"][i]
            open_prev = state["open"][i]
            q, k, v, g = sparse_project(x, p, c)
            with jax.named_scope("select"):
                # this token's key into the open segment; where it closes
                # one (and a window with it), the window's key into the
                # pool, before the step ranks by it
                seg = open_prev.at[:, :, 1].add(k)
                closes = (total % c.kernel_stride == 0) & live
                j = total // c.kernel_stride - 2    # the window that closed
                ckey = (seg[:, :, 0] + seg[:, :, 1]) / c.kernel_size
                page = jnp.take_along_axis(
                    table, jnp.maximum(j, 0)[:, None] // n, axis=1)
                page = jnp.where(closes & (j >= 0), page[:, 0],
                                 cpool.shape[0])             # (else dropped)
                cols = ((heads * n + (j % n)[:, None, None]) * D
                        + jnp.arange(D)[None, None, :])      # (B, Hkv, D)
                cpool = cpool.at[page[:, None, None], cols].set(
                    ckey, mode="drop")
                shifted = jnp.stack([seg[:, :, 1], jnp.zeros_like(
                    seg[:, :, 1])], axis=2)
                seg = jnp.where(closes[:, None, None, None], shifted, seg)
                new["open"].append(jnp.where(live[:, None, None, None], seg,
                                             open_prev))
                new["cpools"].append(cpool)
            qg = q.reshape(B, Hkv, G, D)
            cand, _ = rank_blocks(qg[:, :, :, None], _ckeys_of(cpool[table],
                                                                c),
                                  length[:, None], c)
            with jax.named_scope("select"):
                # (a table narrower than topk never holds a sparse row)
                _, best = jax.lax.top_k(cand[:, :, 0], min(c.topk, NP))
                best = jnp.pad(best, ((0, 0), (0, 0),
                                      (0, c.topk - best.shape[-1])))
                chosen = jnp.concatenate([
                    jnp.broadcast_to(jnp.arange(c.init_blocks)[None, None],
                                     (B, Hkv, c.init_blocks)),
                    best,
                    jnp.broadcast_to(window[:, None], (B, Hkv,
                                                       c.window_blocks))],
                    axis=-1)
                chosen = jnp.pad(chosen, ((0, 0), (0, 0),
                                          (0, GW - c.kept_blocks)))
                blocks = jnp.where(sparse[:, None, None], chosen,
                                   dense_blocks[None, None])
                blocks = jnp.clip(blocks, 0, NP - 1)
                pages = jnp.take_along_axis(
                    jnp.broadcast_to(table[:, None], (B, Hkv, NP)), blocks,
                    axis=2)
                gathered = (pages * Hkv + heads).reshape(B * Hkv, GW)
                lens = jnp.repeat(jnp.where(sparse, sparse_len, total), Hkv)
            with jax.named_scope("sparse_attn"):
                o, k_pool, v_pool = paged_decode_attention_batch(
                    qg.reshape(B * Hkv, G, D), k_pool, v_pool, gathered,
                    lens, k_new=k.reshape(B * Hkv, 1, D),
                    v_new=v.reshape(B * Hkv, 1, D))
            new["pools"].append((k_pool, v_pool))
            x = mlp(_gated_out(x, o.reshape(B, -1), g, p, c), p, c)
        tables = len(new["pools"]) * Hkv
        resident = -(-total // c.block_size)
        seen_keys = jnp.maximum(
            (total - c.kernel_size) // c.kernel_stride + 1, 0)
        counts = jnp.stack([
            jnp.sum(jnp.where(live, jnp.where(sparse, c.kept_blocks,
                                              resident), 0)) * tables,
            jnp.sum(jnp.where(live, resident, 0)) * tables,
            jnp.sum(live & sparse),
            jnp.sum(jnp.where(live & sparse, seen_keys, 0)) * tables])
        return self._head(x), new, counts.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Initialiser and counts
# ---------------------------------------------------------------------------


def init_params(cfg: MiniCpmSalaConfig, key, *, embed_std: float = 0.02,
                in_std: float = 0.02, ffn_out_std: float = 0.02,
                sparse_out_std: float | tuple = 0.02,
                lightning_out_std: float = 0.02,
                head_std: float = 0.02, query_scale: float = 1.0):
    """Seeded random weights, by what a matrix does: the embedding
    normal(0, `embed_std`); those that read the stream (W_in, W13)
    normal(0, `in_std`); W2 normal(0, `ffn_out_std`); a mixer's W_o
    normal(0, `sparse_out_std` | `lightning_out_std`), `sparse_out_std` one
    number or one for each sparse layer in order; the head normal(0,
    `head_std`); every norm's scale 1, but the sparse layers' q-norm:
    `query_scale`, which is what sharpens their softmax.  (Which values a
    benchmark takes, and why, is the benchmark's:
    `benchmarks/families/minicpm_sala.py`.)"""
    model = MiniCpmSalaModel(cfg)
    drawn = jax.eval_shape(lambda: model.init(
        key, jnp.zeros((1, 8), jnp.int32)))
    flat, tree = jax.tree_util.tree_flatten_with_path(drawn)
    keys = jax.random.split(key, len(flat))
    sparse_layers = cfg.layers_of(SPARSE)
    if isinstance(sparse_out_std, (int, float)):
        sparse_out_std = (sparse_out_std,) * len(sparse_layers)
    out = []
    for (path, leaf), k in zip(flat, keys):
        names = [p.key for p in path]
        layer = int(names[1].split("_")[1]) \
            if names[1].startswith("layers_") else None
        kind = None if layer is None else cfg.mixer_types[layer]
        std = {"embed": embed_std, "lm_head": head_std, "in_proj": in_std,
               "w13": in_std, "w2": ffn_out_std,
               "o_proj": sparse_out_std[sparse_layers.index(layer)]
               if kind == SPARSE else lightning_out_std}.get(names[-1])
        if std is not None:
            leaf = (jax.random.normal(k, leaf.shape, jnp.float32)
                    * std).astype(leaf.dtype)
        else:
            leaf = jnp.full(leaf.shape, query_scale if
                            (names[-1], kind) == ("q_norm", SPARSE) else 1.0,
                            leaf.dtype)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


def count_params(cfg: MiniCpmSalaConfig) -> dict:
    """Parameters by kind of layer (one layer of each) and in all."""
    d, D = cfg.d_model, cfg.head_dim
    HD = cfg.n_heads * D
    mlp_ = 3 * d * cfg.d_ff + 2 * d             # + the layer's two norms
    one = {SPARSE: mlp_ + d * (2 * HD + 2 * cfg.n_kv_heads * D) + HD * d
           + 2 * D,
           LIGHTNING: mlp_ + d * 4 * HD + HD * d + 3 * D}
    total = sum(one[k] for k in cfg.mixer_types) \
        + 2 * cfg.vocab_size * d + d
    return dict(one, embedding=cfg.vocab_size * d,
                head=cfg.vocab_size * d, total=total)
