"""Latent-attention decoder with routed and shared experts (`mla_moe`: the
DeepSeek-V3 decoder, as Kimi-VL-A3B's language model has it), TPU-first.

    h = E[token]
    for each layer i:
        h += Attn_i(RMSNorm(h))         latent attention, 16 heads
        h += FF_i(RMSNorm(h))           dense for i < n_dense_layers
    logits = W_head RMSNorm(h)          a head of its own (not tied)

Latent attention (`LatentAttention`), n the normed stream:

    q = W_q n                           a head: [q_nope 128 | q_rope 64]
    [c | k_r] = W_kva n                 512 + 64, ONE of each for all heads
    c' = RMSNorm(c), eps 1e-6;  R = rotary over 64, pairs interleaved
    [k_nope | v]_h = W_kvb,h c'         128 + 128 a head
    score_h(t) = (q_nope . k_nope_t + R(q_rope) . R(k_r)_t) / sqrt(192)
    Attn = W_o concat_h(softmax . v)

What a token leaves behind is `[c' | R(k_r)]`: 576 values a layer, for all
heads.  The cache holds that row (`latent_row`: padded with zeros to whole
lanes, 640), and the two paths read it differently:

  prefill   decompresses: k_nope and v a head from the prompt's own rows,
            then causal attention of the prompt over itself with q and k
            of 192 and v of 128 a head (the flash kernel takes one width:
            v is padded with zeros to 192, a fifth more operations in the
            attention and nothing else).
  decode    absorbs: `q~_h = W_UK,h^T q_nope` (512) so that a score is
            `[q~_h | R(q_rope)] . [c' | R(k_r)]`, and the softmax is taken
            over the latents themselves, `o_h = W_UV,h sum_t p_t c'_t`:
            the same numbers, and a page is read once for all heads, as key
            and as value (`ops/paged_attention.paged_latent_attention_batch`,
            which also writes the step's row in place).

Routed feed-forward: `models/lfm2_moe.py`'s own `RoutedExperts` (`route`:
sigmoid scores, the six largest of score + selection bias, the unbiased
scores renormalised, here over their sum + 1e-20; `expert_ffn`: the (row,
expert) pairs sorted by expert, one grouped product a matrix, no token
dropped; `expert_counts`), its output scaled by `routed_scaling`; and
beside the routed experts every token passes the SHARED experts, one gated
feed-forward of `n_shared x d_expert`:

    FF = routed_scaling * sum_{e in S} g_e E_e(u) + Shared(u)

Precision is `models/lfm2_moe.py`'s: parameters and the cached rows in the
configuration's `dtype`; the router, the norms, the residual stream and the
softmax in float32; an activation enters a product with a weight as two
bfloat16 terms.  Named scopes: `latent_proj`, `latent_attn`, `route`,
`experts`, `shared_expert`, `mlp`, `head`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.lfm2_moe import (RoutedExperts, add_counts,
                                     draw_named)
from ray_tpu.models.llama import RMSNorm
from ray_tpu.models.sambay import Linear, _halves, _two_terms

_NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    vocab_size: int = 163840
    d_model: int = 2048
    n_layers: int = 27
    n_dense_layers: int = 1
    n_heads: int = 16
    d_ff: int = 11264              # the dense layers' feed-forward
    d_expert: int = 1408           # one expert's
    n_experts: int = 64
    top_k: int = 6
    n_shared: int = 2
    kv_rank: int = 512             # the latent
    d_nope: int = 128              # a head's q and k without position
    d_rope: int = 64               # ... and the rotated part
    d_v: int = 128
    rope_theta: float = 800000.0
    norm_eps: float = 1e-5
    # the latent's norm: the published code builds it with its norm
    # class's default and not with the configuration's `rms_norm_eps`
    latent_norm_eps: float = 1e-6
    routed_scaling: float = 2.446
    dtype: Any = jnp.bfloat16
    # whole-prompt attention: "flash" (pallas) or "reference" (plain jnp)
    attention: str = "flash"

    @property
    def d_qk(self) -> int:
        return self.d_nope + self.d_rope

    @property
    def latent_dim(self) -> int:
        """What a token caches a layer: the latent and the rotated key."""
        return self.kv_rank + self.d_rope

    @property
    def latent_row(self) -> int:
        """A cached row: `latent_dim` padded to whole lanes of 128."""
        return -(-self.latent_dim // 128) * 128


KIMI_VL_A3B = MlaMoeConfig()
TINY_MLA_MOE = MlaMoeConfig(
    vocab_size=256, d_model=64, n_layers=3, n_heads=4, d_ff=128,
    d_expert=32, n_experts=8, top_k=3, n_shared=2, kv_rank=32, d_nope=16,
    d_rope=8, d_v=16, dtype=jnp.float32, attention="reference")


def sm_scale(cfg: MlaMoeConfig) -> float:
    """Scores are divided by the root of a head's WHOLE query width."""
    return 1.0 / math.sqrt(cfg.d_qk)


def rotate_interleaved(x, positions, theta: float):
    """x (..., d), positions (...), broadcast against x's leading axes:
    the rotary term over pairs of ADJACENT values, (x[2i], x[2i+1]) turned
    by position / theta^(2i/d).
    What comes back is laid out evens first, then odds (the published
    code's order; a score is a sum over pairs, so q and k need only
    agree)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x = x.astype(jnp.float32)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _heads(x, w, spec: str):
    """An einsum of an activation with a head-wise weight as `matmul`
    makes a product: two bfloat16 terms along x's leading axis where the
    weight is bfloat16, float32 out."""
    if w.dtype == jnp.float32:
        return jnp.einsum(spec, x.astype(jnp.float32), w)
    return _halves(jnp.einsum(spec, _two_terms(x, 0), w,
                              preferred_element_type=jnp.float32), 0)


def causal_mixed_width(q, k, v, scale: float, impl: str, dtype):
    """Causal attention of a prompt over itself where q and k (B, H, S,
    d_qk) are wider than v (B, H, S, d_v), all float32 -> (B, H, S, d_v)
    float32.  "flash": the kernel takes them in `dtype`."""
    if impl == "flash":
        from ray_tpu.ops.attention import causal_over_itself

        # The kernel takes one width and scales by the root of it, which
        # IS d_qk: v rides padded with zeros, cut off again below.
        pad = q.shape[-1] - v.shape[-1]
        out = causal_over_itself(
            q.astype(dtype), k.astype(dtype),
            jnp.pad(v, ((0, 0),) * 3 + ((0, pad),)).astype(dtype))
        return out[..., : v.shape[-1]].astype(jnp.float32)
    S = q.shape[2]
    s = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    seen = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(seen, s, _NEG_INF), axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", p, v.astype(jnp.float32))


class LatentAttention(nn.Module):
    cfg: MlaMoeConfig

    def setup(self):
        c = self.cfg
        self.q_proj = Linear(c.n_heads * c.d_qk, c.dtype)
        self.kv_a_proj = Linear(c.latent_dim, c.dtype)
        self.kv_norm = RMSNorm(c.latent_norm_eps)
        # (latent, head, [k_nope | v]): W_UK and W_UV side by side
        self.kv_b = self.param("kv_b", nn.initializers.normal(0.02),
                               (c.kv_rank, c.n_heads, c.d_nope + c.d_v),
                               c.dtype)
        self.o_proj = Linear(c.d_model, c.dtype)

    def project(self, u, positions):
        """u (B, S, d), positions (B, S) -> q_nope (B, S, H, d_nope) and
        the rotated q_rope (B, S, H, d_rope), float32; and the rows the
        cache holds, (B, S, latent_row) in its type: [c' | R(k_r) | 0]."""
        c = self.cfg
        B, S, _ = u.shape
        with jax.named_scope("latent_proj"):
            q = self.q_proj(u, precise=True).reshape(B, S, c.n_heads, c.d_qk)
            q_rope = rotate_interleaved(q[..., c.d_nope:],
                                        positions[..., None], c.rope_theta)
            kv = self.kv_a_proj(u, precise=True)
            rows = jnp.concatenate(
                [self.kv_norm(kv[..., : c.kv_rank]),
                 rotate_interleaved(kv[..., c.kv_rank:], positions,
                                    c.rope_theta)], axis=-1)
            rows = jnp.pad(rows, ((0, 0), (0, 0),
                                  (0, c.latent_row - c.latent_dim)))
        return q[..., : c.d_nope], q_rope, rows.astype(c.dtype)

    def over_itself(self, q_nope, q_rope, rows):
        """The prompt over itself, decompressed: a head's k_nope and v from
        the rows as the cache holds them -> (B, S, d)."""
        c = self.cfg
        B, S, H, _ = q_nope.shape
        with jax.named_scope("latent_attn"):
            # (the latent is in the weight's type already: one term)
            kv = jnp.einsum("bsc,chn->bshn", rows[..., : c.kv_rank],
                            self.kv_b, preferred_element_type=jnp.float32)
            k_rope = jnp.broadcast_to(
                rows[:, :, None, c.kv_rank: c.latent_dim],
                (B, S, H, c.d_rope)).astype(jnp.float32)
            heads_first = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
            o = causal_mixed_width(
                heads_first(jnp.concatenate([q_nope, q_rope], -1)),
                heads_first(jnp.concatenate([kv[..., : c.d_nope], k_rope],
                                            -1)),
                heads_first(kv[..., c.d_nope:]), sm_scale(c), c.attention,
                c.dtype)
            o = heads_first(o).reshape(B, S, H * c.d_v)
        return self.o_proj(o, precise=True)

    def over_pages(self, q_nope, q_rope, row, pool, table, length):
        """One token a sequence over its pages, absorbed: q_nope (B, H,
        d_nope), q_rope (B, H, d_rope), the token's own row (B,
        latent_row), `length` (B,) tokens with it -> ((B, d), the pool
        with the row in it)."""
        from ray_tpu.ops.paged_attention import paged_latent_attention_batch

        c = self.cfg
        with jax.named_scope("latent_attn"):
            w_uk, w_uv = self.kv_b[..., : c.d_nope], self.kv_b[..., c.d_nope:]
            q = jnp.concatenate([_heads(q_nope, w_uk, "bhn,chn->bhc"),
                                 q_rope], axis=-1)
            q = jnp.pad(q, ((0, 0), (0, 0), (0, c.latent_row - c.latent_dim)))
            o, pool = paged_latent_attention_batch(
                q, pool, table, length, row, d_value=c.kv_rank,
                sm_scale=sm_scale(c))
            o = _heads(o, w_uv, "bhc,chv->bhv").reshape(o.shape[0], -1)
        return self.o_proj(o, precise=True), pool


class GatedMLP(nn.Module):
    """`W2 (silu(W1 u) * W3 u)` of width `width`, W1 and W3 side by side."""
    width: int
    dtype: Any
    d_model: int
    label: str = "mlp"

    def setup(self):
        self.w13 = Linear(2 * self.width, self.dtype)
        self.w2 = Linear(self.d_model, self.dtype)

    def __call__(self, u):
        with jax.named_scope(self.label):
            a, b = jnp.split(self.w13(u, precise=True), 2, axis=-1)
            return self.w2(nn.silu(a) * b, precise=True)


class Layer(nn.Module):
    cfg: MlaMoeConfig
    routed: bool

    def setup(self):
        c = self.cfg
        self.attn_norm = RMSNorm(c.norm_eps)
        self.ffn_norm = RMSNorm(c.norm_eps)
        self.attn = LatentAttention(c)
        if self.routed:
            self.experts = RoutedExperts(c, eps=1e-20)
            self.shared = GatedMLP(c.n_shared * c.d_expert, c.dtype,
                                   c.d_model, "shared_expert")
        else:
            self.mlp = GatedMLP(c.d_ff, c.dtype, c.d_model)

    def feed_forward(self, x, counts, valid=None):
        """x += FF(norm(x)) -> (x, the counts with this layer's in them)."""
        u = self.ffn_norm(x)
        if not self.routed:
            return x + self.mlp(u), counts
        out, mine = self.experts(u, valid)
        return x + out + self.shared(u), \
            mine if counts is None else add_counts(counts, mine)


class MlaMoeModel(nn.Module):
    cfg: MlaMoeConfig

    def setup(self):
        c = self.cfg
        self.embed = nn.Embed(c.vocab_size, c.d_model, dtype=c.dtype,
                              param_dtype=c.dtype)
        self.layers = [Layer(c, i >= c.n_dense_layers)
                       for i in range(c.n_layers)]
        self.norm = RMSNorm(c.norm_eps)
        self.lm_head = Linear(c.vocab_size, c.dtype)

    def _head(self, x):
        with jax.named_scope("head"):
            return self.lm_head(self.norm(x), precise=True)

    def _rows(self, tokens, last_idx=None):
        """Every layer over (B, S) tokens -> the stream (B, S, d), each
        layer's cached rows (B, S, latent_row), the routed layers' counts.
        Positions past `last_idx` are given to no expert."""
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        valid = None if last_idx is None \
            else positions <= last_idx[:, None]
        x = self.embed(tokens).astype(jnp.float32)
        rows, counts = [], None
        for layer in self.layers:
            q_nope, q_rope, cached = layer.attn.project(
                layer.attn_norm(x), positions)
            x = x + layer.attn.over_itself(q_nope, q_rope, cached)
            x, counts = layer.feed_forward(x, counts, valid)
            rows.append(cached)
        return x, rows, counts

    def __call__(self, tokens):
        """Whole forward: (B, S) -> float32 logits (B, S, V)."""
        return self._head(self._rows(tokens)[0])

    def prefill(self, tokens, last_idx):
        """Right-padded rows (B, S) with each row's last token at
        `last_idx` -> float32 logits (B, V) at that token; each layer's
        rows for the cache, (B, S, latent_row) over the whole row; and the
        routed layers' counts (`lfm2_moe.EXPERT_COUNTS`) over the rows'
        real tokens."""
        x, rows, counts = self._rows(tokens, last_idx)
        last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
        return self._head(last), rows, counts

    def decode(self, token, pos, pools, table, length, live=None):
        """One token a sequence: token (B,), `length` (B,) tokens already
        cached at positions `pos` (B,); pools: a layer's (P, page,
        latent_row) under `table` (B, NP) -> float32 logits (B, V), the
        pools with this token in them, the step's counts.  A row where
        `live` is False is given to no expert (its row lands where its
        next live step writes again)."""
        x = self.embed(token).astype(jnp.float32)[:, None]  # (B, 1, d)
        valid = None if live is None else live[:, None]
        out, counts = [], None
        for layer, pool in zip(self.layers, pools):
            q_nope, q_rope, row = layer.attn.project(
                layer.attn_norm(x), pos[:, None])
            o, pool = layer.attn.over_pages(
                q_nope[:, 0], q_rope[:, 0], row[:, 0], pool, table,
                length + 1)
            x, counts = layer.feed_forward(x + o[:, None], counts, valid)
            out.append(pool)
        return self._head(x[:, 0]), out, counts


def init_params(cfg: MlaMoeConfig, key, *, embed_std: float = 0.02,
                in_std: float = 0.02, q_std: float = 0.02,
                kv_a_std: float = 0.02, kv_b_std: float = 0.02,
                out_std: float = 0.02, ffn_out_std: float = 0.02,
                expert_out_std: float = 0.02, shared_out_std: float = 0.02,
                router_std: float = 0.02, bias_std: float = 0.0,
                head_std: float = 0.02):
    """Seeded random weights, each kind normal(0, its std): the embedding;
    W1 | W3 of the dense feed-forward, of every expert and of the shared
    experts (`in_std`); W_q, W_kva and W_kvb; the attention's
    out-projection (`out_std`); W2 of the dense feed-forward, of every
    expert, of the shared experts; the router; the selection bias (zero,
    as a fresh buffer is, at 0); the head.  Norm scales are 1.  (Which
    values a benchmark takes, and why, is the benchmark's.)"""
    drawn = MlaMoeModel(cfg).init(key, jnp.zeros((1, 8), jnp.int32))
    return draw_named(drawn, key, {
        "embed": embed_std, "q_proj": q_std, "kv_a_proj": kv_a_std,
        "kv_b": kv_b_std, "o_proj": out_std, "w13": in_std,
        "mlp/w2": ffn_out_std, "experts/w2": expert_out_std,
        "shared/w2": shared_out_std, "router": router_std,
        "expert_bias": bias_std, "lm_head": head_std})


def count_params(cfg: MlaMoeConfig) -> dict:
    """Parameters by part (one of each) and in all."""
    d = cfg.d_model
    one = {
        "attention": d * cfg.n_heads * cfg.d_qk + d * cfg.latent_dim
        + cfg.kv_rank + cfg.kv_rank * cfg.n_heads * (cfg.d_nope + cfg.d_v)
        + cfg.n_heads * cfg.d_v * d,
        "dense_ffn": 3 * d * cfg.d_ff,
        "expert": 3 * d * cfg.d_expert,
        "shared": 3 * d * cfg.n_shared * cfg.d_expert,
        "router": d * cfg.n_experts + cfg.n_experts,
    }
    routed = cfg.n_experts * one["expert"] + one["shared"] + one["router"]
    total = cfg.n_layers * (one["attention"] + 2 * d) \
        + cfg.n_dense_layers * one["dense_ffn"] \
        + (cfg.n_layers - cfg.n_dense_layers) * routed \
        + 2 * cfg.vocab_size * d + d
    return dict(one, routed_layer=routed, embedding=cfg.vocab_size * d,
                total=total)
