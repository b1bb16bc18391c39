"""Granite-4.0-H decoder (`granitemoehybrid`), TPU-first: Mamba-2 layers
with a few plain grouped-query attention layers among them, every layer
followed by a gated feed-forward: the shared one alone (the family's dense
members, `n_experts` 0) or routed experts beside it.

    h = embedding_multiplier * E[token]
    for each layer, by `layer_types`:
        h += residual_multiplier * Mixer(RMSNorm(h))    Mamba-2 | attention
        h += residual_multiplier * FF(RMSNorm(h))       shared [+ routed]
    logits = RMSNorm(h) E^T / logits_scaling            the embedding tied

Routed feed-forward (`route`, then `models/lfm2_moe.RoutedExperts`): plain
router logits `W_r u` (no bias, no sigmoid), the `top_k` largest, and the
gates a softmax over THOSE logits alone; `FF = shared(u) + sum_{e chosen}
g_e W2_e (silu(a_e) * b_e)`, no capacity and no scaling factor.  Where a
layer's experts are divided over chips (`experts_held` = (first, count)),
this chip routes over all `n_experts`, computes the pairs whose expert it
holds and adds ITS PART of the routed sum; the other chips' parts are an
exchange this file does not have (`PERF.md` section 7).

Attention: no position term, causal, scores scaled by `attention_multiplier`
(1/64 or 1/128 published, not 1/sqrt(head size)).  Heads of 128 are the
kernels' own and run as they are.  Narrower heads (64 published) run on the
kernels' heads of 128 with no padding in the cache: KV heads (2j, 2j+1) lie
as ONE head of 128, K' = [k_2j | k_2j+1] and V' likewise, and a query head
is padded with zeros on the half it does not use, [q | 0] or [0 | q].  Then
q' . K' = q . k exactly, every query head still has a softmax of its own,
and its output is the half of p V' = [p v_2j | p v_2j+1] that belongs to
its KV head.  (`models/sambay.py` pairs differential heads the same way;
plain GQA needs only the choice of half at the end.)  The flash and paged
kernels of `ray_tpu/ops` run unchanged; the cost is twice the attention
arithmetic, in four layers of forty.

Mamba-2 (Dao & Gu 2024), one group: `[z | xBC | dt] = W_in h`;
`xBC = silu(conv4(xBC) + b)`, depthwise and causal; `x, B, C = split`;
`dt = softplus(dt + dt_bias)` and `A = -exp(A_log)`, a scalar a head; for
head i with x_t[i] in R^P and the state S[i] in R^(P x N)

    S_t[i] = exp(dt_t[i] A[i]) S_{t-1}[i] + dt_t[i] x_t[i] (x) B_t
    y_t[i] = S_t[i] C_t + D[i] x_t[i]

then `y = RMSNorm_w(y * silu(z))` over the whole inner width and `W_out y`.
Over a prompt the recurrence runs in its chunked matrix form (`ssd_scan`:
inside a chunk one masked matrix of decays times C B^T, between chunks
the state); one token at a time it is the recurrence itself
(`Mamba2.step`), which reads and writes each sequence's (H, P, N) float32
state once.  A sequence's decode state is, a Mamba layer, that state and
the last `d_conv - 1` inputs of the conv; an attention layer, pages.

Precision (`models/sambay.py`'s `matmul` is this file's): parameters and
what is stored between steps (K, V, conv windows) are the configuration's
`dtype`; the residual stream, norms, softmax, the decay, the scan and the
state are float32.  An activation enters every product with a weight as
two bfloat16 terms, over a prompt as in a decode step, and the scan's own
products are float32 at the highest precision: forty layers of single-term
products put 5% of the logits' deviation on a prompt's last token, and a
state that remembers thousands of steps carries its share of that through
every decode step after (PR 36; `PERF.md` section 6).  The flash kernel
takes q, k and v in `dtype`.

Every part runs under a `jax.named_scope` (`ssd_scan`, `state_step`,
`attention`, `mlp` or, beside routed experts, `shared_expert`, `route`,
`experts`, `head`), so a profile's operation names carry them.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.lfm2_moe import RoutedExperts, add_counts
from ray_tpu.models.llama import apply_rope, rope_frequencies
from ray_tpu.models.sambay import Linear, causal_attention, matmul

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    d_model: int = 2048
    layer_types: tuple = _PERIOD * 4
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 8192               # the shared feed-forward
    # Routed experts beside it: the router's width (0: none), the experts a
    # token chooses, one expert's width, and (first, count): the experts
    # THIS chip holds where a layer's are divided over chips (None: all).
    n_experts: int = 0
    top_k: int = 0
    d_expert: int = 0
    experts_held: tuple | None = None
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    d_state: int = 128
    d_conv: int = 4
    chunk: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    # None as published (no position term).  Set, q and k are rotated: only
    # so that tests can plant "rotary applied" and see it refused.
    rope_theta: float | None = None
    max_positions: int = 131072
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # whole-prompt attention: "flash" (pallas) or "reference" (plain jnp)
    attention: str = "flash"

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def paired(self) -> bool:
        """Whether two KV heads lie in one head of the kernels' width (the
        module's head): where the heads are narrower than 128."""
        return self.head_dim < 128

    @property
    def kv_pool_heads(self) -> tuple:
        """(heads, width) of the K and V a cache holds for one layer."""
        return (self.n_kv_heads // 2, 2 * self.head_dim) if self.paired \
            else (self.n_kv_heads, self.head_dim)

    @property
    def experts_here(self) -> int:
        return self.experts_held[1] if self.experts_held else self.n_experts

    # (`RoutedExperts` reads it: this family's routed sum is not scaled)
    routed_scaling = 1.0

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the conv: x, B and C together."""
        return self.d_inner + 2 * self.d_state

    def layers_of(self, kind: str) -> list:
        return [i for i, k in enumerate(self.layer_types) if k == kind]


GRANITE_4_H_MICRO = GraniteHybridConfig()
TINY_GRANITE = GraniteHybridConfig(
    vocab_size=256, d_model=64, layer_types=("mamba", "mamba", "attention",
                                             "mamba") * 2,
    n_heads=4, n_kv_heads=2, d_ff=128, mamba_heads=4, mamba_head_dim=32,
    d_state=16, chunk=8, max_positions=256, dtype=jnp.float32,
    attention="reference")
# The routed member at tiny widths: 8 experts of 32, three a token, beside
# a shared one of 64; 4 query and 2 KV heads of 128 as they are (nothing
# paired), so a stream of 512.
TINY_GRANITE_MOE = dataclasses.replace(
    TINY_GRANITE, d_model=512, d_ff=64, n_experts=8, top_k=3, d_expert=32,
    logits_scaling=16.0)


class RMSNorm(nn.Module):
    """Float32 in and out (the stream is float32)."""
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        xf = x.astype(jnp.float32)
        return xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps) * scale


def route(logits, top_k: int):
    """Router logits (T, E) float32 -> the chosen experts (T, k) and their
    gates (T, k), float32: the k largest LOGITS, and a softmax over those
    k alone (not over all E: the unchosen take no share of it)."""
    top, idx = jax.lax.top_k(logits.astype(jnp.float32), top_k)
    return idx, jax.nn.softmax(top, axis=-1)


class MLP(nn.Module):
    """`W_out (silu(a) * b)`, `(a, b) = split(W_in x)`: the layer's whole
    feed-forward, or the shared expert beside the routed ones."""
    cfg: GraniteHybridConfig

    def setup(self):
        c = self.cfg
        self.in_proj = Linear(2 * c.d_ff, c.dtype)
        self.out_proj = Linear(c.d_model, c.dtype)

    def __call__(self, x):
        with jax.named_scope("shared_expert" if self.cfg.n_experts
                             else "mlp"):
            a, b = jnp.split(self.in_proj(x, precise=True), 2, axis=-1)
            return self.out_proj(nn.silu(a) * b, precise=True)


# ---------------------------------------------------------------------------
# Attention: heads of 128 as they are, heads of 64 as halves of heads of 128
# (see the module's head)
# ---------------------------------------------------------------------------


class Attention(nn.Module):
    cfg: GraniteHybridConfig

    def setup(self):
        c = self.cfg
        if (c.paired and c.n_kv_heads % 2) or c.n_heads % c.n_kv_heads:
            raise ValueError("KV heads pair up, and whole groups of query "
                             "heads share a KV head")
        self.qkv_proj = Linear((c.n_heads + 2 * c.n_kv_heads) * c.head_dim,
                               c.dtype)
        self.o_proj = Linear(c.d_model, c.dtype)

    def project(self, h, positions):
        """h (B, S, d) -> q' (B, Hq, S, 2 Dh) zero-padded on the half it
        does not use, K' and V' (B, Hkv/2, S, 2 Dh) in the type a cache
        holds them in; where nothing is paired q (B, Hq, S, Dh), K and V
        (B, Hkv, S, Dh).  `positions` (B, S) are read only where the
        configuration rotates."""
        c = self.cfg
        B, S, _ = h.shape
        Dh, Hq, Hkv = c.head_dim, c.n_heads, c.n_kv_heads
        q, k, v = jnp.split(self.qkv_proj(h, precise=True),
                            [Hq * Dh, (Hq + Hkv) * Dh], axis=-1)
        q = q.reshape(B, S, Hq, Dh)
        k = k.reshape(B, S, Hkv, Dh)
        if c.rope_theta is not None:
            cos, sin = rope_frequencies(Dh, c.max_positions, c.rope_theta)
            rot = lambda a: apply_rope(  # noqa: E731
                a.transpose(0, 2, 1, 3).astype(jnp.float32), cos, sin,
                positions).transpose(0, 2, 1, 3)
            q, k = rot(q), rot(k)
        if not c.paired:
            k, v = (a.astype(c.dtype).reshape(B, S, Hkv, Dh)
                    .transpose(0, 2, 1, 3) for a in (k, v))
            return q.transpose(0, 2, 1, 3), k, v
        k, v = (a.astype(c.dtype).reshape(B, S, Hkv // 2, 2 * Dh)
                .transpose(0, 2, 1, 3) for a in (k, v))
        # query heads of KV head 2j use the left half, of 2j + 1 the right
        q = q.reshape(B, S, Hkv // 2, 2, Hq // Hkv, Dh)
        zeros = jnp.zeros_like(q[:, :, :, 0])
        q = jnp.stack([jnp.concatenate([q[:, :, :, 0], zeros], -1),
                       jnp.concatenate([zeros, q[:, :, :, 1]], -1)], axis=3)
        return q.reshape(B, S, Hq, 2 * Dh).transpose(0, 2, 1, 3), k, v

    def combine(self, attn):
        """attn (B, Hq, S, 2 Dh), each query head's softmax applied to
        [v_2j | v_2j+1] -> its own half -> the layer's output (B, S, d);
        (B, Hq, S, Dh) where nothing is paired."""
        c = self.cfg
        B, Hq, S, D2 = attn.shape
        Dh, G = c.head_dim, c.n_heads // c.n_kv_heads
        if not c.paired:
            return self.o_proj(attn.transpose(0, 2, 1, 3).reshape(
                B, S, c.d_model), precise=True)
        a = attn.reshape(B, c.n_kv_heads // 2, 2, G, S, 2, Dh)
        o = jnp.stack([a[:, :, 0, :, :, 0], a[:, :, 1, :, :, 1]], axis=2)
        o = o.reshape(B, Hq, S, Dh).transpose(0, 2, 1, 3)
        return self.o_proj(o.reshape(B, S, c.d_model), precise=True)


# ---------------------------------------------------------------------------
# Mamba-2
# ---------------------------------------------------------------------------


def ssd_scan(x, dt, a, b_sel, c_sel, s0=None, *, chunk: int):
    """The Mamba-2 recurrence over whole rows in its chunked matrix form.

    x (B, S, H, P); dt (B, S, H) float32, >= 0; a (H,) negative; b_sel,
    c_sel (B, S, N) (one group: every head reads the same B and C) or
    (B, S, H, N) (a head its own: linear attention, B its keys and C its
    queries, `models/minicpm_sala.py`); s0 (B, H, P, N) float32 or None
    (zeros).  Returns y (B, S, H, P) float32 WITHOUT the skip term, and the
    state after the last position.  A position whose dt is 0 leaves the
    state as it was (decay 1, drive 0): that is how a right-padded row
    keeps the state of its own last token.

    With l_t = cumsum(dt_t a) inside a chunk of Q positions:
      inside    y_t += sum_{s<=t} exp(l_t - l_s) (C_t . B_s) dt_s x_s
      carried   y_t += exp(l_t) C_t . S_in,
      S_out = exp(l_Q) S_in + sum_s exp(l_Q - l_s) dt_s x_s (x) B_s
    Everything is float32, the decays differences of l (never ratios of
    exponentials) and the products at the highest precision: 3 MFLOP a
    token and layer beside 152 of projections, and what they round the
    state keeps."""
    B, S, H, P = x.shape
    Q = min(chunk, S)
    pad = -S % Q
    if pad:
        x, dt, b_sel, c_sel = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b_sel, c_sel))
    nc = (S + pad) // Q
    x, b_sel, c_sel = (v.astype(jnp.float32) for v in (x, b_sel, c_sel))
    exact = dict(precision=jax.lax.Precision.HIGHEST)
    x, dt, b_sel, c_sel = (v.reshape(B, nc, Q, *v.shape[2:])
                           for v in (x, dt, b_sel, c_sel))
    # B and C of a position: "n" where the heads share them, "hn" a head
    n = "n" if b_sel.ndim == 4 else "hn"
    l = jnp.cumsum(dt * a, axis=2)                          # (B, nc, Q, H)
    drive = dt[..., None] * x                               # dt_s x_s
    # inside a chunk
    scores = jnp.einsum(f"bct{n},bcs{n}->bc{n[:-1]}ts", c_sel, b_sel, **exact)
    if n == "n":
        scores = scores[:, :, None]
    lh = l.transpose(0, 1, 3, 2)                            # (B, nc, H, Q)
    seen = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(seen, lh[..., :, None] - lh[..., None, :],
                              -jnp.inf))                    # (B, nc, H, t, s)
    y = jnp.einsum("bchts,bcshp->bcthp", decay * scores, drive, **exact)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(l[:, :, -1:, :] - l)                   # (B, nc, Q, H)
    own = jnp.einsum(f"bcshp,bcs{n}->bchpn", to_end[..., None] * drive,
                     b_sel, **exact)
    # between chunks: the state each chunk starts from
    whole = jnp.exp(l[:, :, -1, :])                         # (B, nc, H)
    s = jnp.zeros((B, H, P, b_sel.shape[-1]), jnp.float32) if s0 is None \
        else s0.astype(jnp.float32)
    starts = []
    for c in range(nc):
        starts.append(s)
        s = whole[:, c, :, None, None] * s + own[:, c]
    s_in = jnp.stack(starts, axis=1)                        # (B, nc, H, P, N)
    y = y + jnp.einsum(f"bct{n},bchpn->bcthp", c_sel, s_in, **exact) \
        * jnp.exp(l)[..., None]
    return y.reshape(B, nc * Q, H, P)[:, :S], s


def state_step(s_prev, decay, drive, b_sel, c_sel):
    """The recurrence itself, one token: s_prev (B, H, P, N) float32, decay
    (B, H) or (H,), drive (B, H, P) (dt x), b_sel and c_sel (B, N) or (B, H,
    N) as `ssd_scan` takes them -> y (B, H, P) without the skip term, and
    the new state.  Each sequence's state is read and written once."""
    if b_sel.ndim == 2:
        b_sel, c_sel = b_sel[:, None], c_sel[:, None]
    s = decay[..., None, None] * s_prev \
        + drive[..., None] * b_sel[:, :, None, :]
    return jnp.sum(s * c_sel[:, :, None, :], axis=-1), s


class Mamba2(nn.Module):
    """State of one sequence: the last d_conv - 1 inputs of the conv,
    (d_conv - 1, conv_dim) in the served type, and S (H, P, N) float32."""
    cfg: GraniteHybridConfig

    def setup(self):
        c = self.cfg
        H = c.mamba_heads
        self.in_proj = Linear(c.d_inner + c.conv_dim + H, c.dtype)
        self.conv_w = self.param("conv_w", nn.initializers.normal(0.2),
                                 (c.d_conv, c.conv_dim), c.dtype)
        self.conv_b = self.param("conv_b", nn.initializers.zeros,
                                 (c.conv_dim,), c.dtype)
        self.dt_bias = self.param("dt_bias", _dt_bias_init, (H,),
                                  jnp.float32)
        self.a_log = self.param("a_log", _a_log_init, (H,), jnp.float32)
        self.d_skip = self.param("d_skip", nn.initializers.ones, (H,),
                                 jnp.float32)
        self.norm = RMSNorm(c.norm_eps)
        self.out_proj = Linear(c.d_model, c.dtype)

    def _split_in(self, h):
        c = self.cfg
        return jnp.split(self.in_proj(h, precise=True),
                         [c.d_inner, c.d_inner + c.conv_dim], axis=-1)

    def _split_conv(self, xbc):
        c = self.cfg
        x, b_sel, c_sel = jnp.split(
            xbc, [c.d_inner, c.d_inner + c.d_state], axis=-1)
        return (x.reshape(*x.shape[:-1], c.mamba_heads, c.mamba_head_dim),
                b_sel, c_sel)

    def _step_size(self, dt):
        return jax.nn.softplus(dt.astype(jnp.float32) + self.dt_bias)

    def _gate_out(self, y, z):
        """y (..., H, P) float32 -> the layer's output."""
        y = y.reshape(*y.shape[:-2], self.cfg.d_inner)
        return self.out_proj(
            self.norm(y * jax.nn.silu(z.astype(jnp.float32))),
            precise=True)

    def __call__(self, h, last_idx=None):
        """h (B, S, d) -> (out, state): state = (conv window, S) after each
        row's `last_idx` (after its last position where None)."""
        c = self.cfg
        B, S, _ = h.shape
        K = c.d_conv
        f32 = lambda v: v.astype(jnp.float32)  # noqa: E731
        z, xbc_in, dt = self._split_in(h)
        with jax.named_scope("ssd_scan"):
            xbc_pad = jnp.pad(xbc_in, ((0, 0), (K - 1, 0), (0, 0)))
            xbc = sum(f32(xbc_pad[:, i: i + S]) * f32(self.conv_w[i])
                      for i in range(K))
            x, b_sel, c_sel = self._split_conv(
                jax.nn.silu(xbc + f32(self.conv_b)))
            dt = self._step_size(dt)
            if last_idx is None:
                last_idx = jnp.full((B,), S - 1, jnp.int32)
            # A position past a row's last token leaves the state alone.
            dt = jnp.where(jnp.arange(S)[None, :, None]
                           <= last_idx[:, None, None], dt, 0.0)
            y, s_last = ssd_scan(x, dt, -jnp.exp(self.a_log), b_sel, c_sel,
                                 chunk=c.chunk)
            y = y + self.d_skip[:, None] * x
            # padded position p holds input p - (K - 1): the K - 1 inputs
            # that end at last_idx are padded positions last_idx + 1 ...
            window = jnp.take_along_axis(
                xbc_pad, (last_idx[:, None] + 1 + jnp.arange(K - 1))[
                    :, :, None], axis=1).astype(c.dtype)
        return self._gate_out(y, z), (window, s_last)

    def step(self, h, state, live=None):
        """One token: h (B, d), state as above -> (out, new state).  A row
        where `live` is False keeps its state."""
        conv, s_prev = state
        f32 = lambda v: v.astype(jnp.float32)  # noqa: E731
        z, xbc_in, dt = self._split_in(h)
        with jax.named_scope("state_step"):
            # (the stored inputs are the served type's; this step's is
            # not rounded before it is used)
            window = jnp.concatenate([f32(conv), f32(xbc_in)[:, None]],
                                     axis=1)
            xbc = jax.nn.silu(jnp.sum(window * f32(self.conv_w), axis=1)
                              + f32(self.conv_b))
            x, b_sel, c_sel = self._split_conv(xbc)         # x (B, H, P)
            dt = self._step_size(dt)                        # (B, H)
            decay = jnp.exp(dt * -jnp.exp(self.a_log))
            y, s = state_step(s_prev, decay, dt[:, :, None] * x, b_sel, c_sel)
            y = y + self.d_skip[:, None] * x
            new = (window[:, 1:].astype(conv.dtype), s)
            if live is not None:
                new = (jnp.where(live[:, None, None], new[0], conv),
                       jnp.where(live[:, None, None, None], s, s_prev))
        return self._gate_out(y, z), new


def _dt_bias_init(key, shape, dtype=jnp.float32, lo=1e-3, hi=1e-1):
    """softplus^-1 of a step size drawn log-uniformly from [lo, hi]
    ([1e-3, 1e-1] is Mamba-2's published initialiser)."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (math.log(hi) - math.log(lo)) + math.log(lo))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
    """A = -uniform(1, 16) a head (Mamba-2's published initialiser)."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


# ---------------------------------------------------------------------------
# The stack
# ---------------------------------------------------------------------------


# What a routed layer counts of one call: `lfm2_moe.EXPERT_COUNTS` over the
# experts HELD here (touched, held, the most rows one took, the (row,
# expert) pairs that lay in a held group), then the pairs the router made.
EXPERT_COUNTS = ("experts_touched", "expert_slots", "expert_rows_max",
                 "expert_rows", "expert_pairs")


def expert_counts_of(held, u, valid, top_k: int):
    """`EXPERT_COUNTS` of one layer's call over rows u: what
    `RoutedExperts` counted of the experts held, and `top_k` pairs for
    every row that is `valid` (None: every row)."""
    rows = math.prod(u.shape[:-1]) if valid is None else jnp.sum(valid)
    return jnp.concatenate(
        [held, (jnp.int32(top_k) * rows).astype(jnp.int32)[None]])


def _sum_counts(a, b):
    """Two layers' counts as one (None: no routed layer there)."""
    if a is None or b is None:
        return b if a is None else a
    return jnp.concatenate([add_counts(a[:4], b[:4]), a[4:] + b[4:]])


class Layer(nn.Module):
    """One decoder block; its mixer is what `layer_types` says."""
    cfg: GraniteHybridConfig
    kind: str

    def setup(self):
        c = self.cfg
        self.input_norm = RMSNorm(c.norm_eps)
        self.post_norm = RMSNorm(c.norm_eps)
        self.mlp = MLP(c)
        if c.n_experts:
            self.experts = RoutedExperts(c, choose=route,
                                         held=c.experts_held)
        if self.kind == "mamba":
            self.mamba = Mamba2(c)
        elif self.kind == "attention":
            self.attn = Attention(c)
        else:
            raise ValueError(f"layer_types holds {self.kind!r}: a layer is "
                             "'mamba' or 'attention'")

    def mix(self, x, mixer, valid=None):
        """x += r * mixer(norm(x)); x += r * FF(norm(x)), the stream in
        float32; whatever else the mixer returns is handed back beside x
        and, last, a routed layer's counts (`expert_counts_of`; rows that
        are not `valid` go to no expert; None where there is none)."""
        r = self.cfg.residual_multiplier
        out, *rest = mixer(self.input_norm(x))
        x = x + r * out
        if not self.cfg.n_experts:
            return (x + r * self.mlp(self.post_norm(x)), *rest, None)
        u = self.post_norm(x)
        routed, counts = self.experts(u, valid)
        return (x + r * (routed + self.mlp(u)), *rest,
                expert_counts_of(counts, u, valid, self.cfg.top_k))


class GraniteHybridModel(nn.Module):
    cfg: GraniteHybridConfig

    def setup(self):
        c = self.cfg
        self.embed = nn.Embed(c.vocab_size, c.d_model, dtype=c.dtype,
                              param_dtype=c.dtype)
        self.layers = [Layer(c, kind) for kind in c.layer_types]
        self.norm = RMSNorm(c.norm_eps)

    def _embed(self, tokens):
        return self.embed(tokens).astype(jnp.float32) \
            * self.cfg.embedding_multiplier

    def _head(self, x):
        with jax.named_scope("head"):
            return matmul(self.norm(x), self.embed.embedding.T, True) \
                / self.cfg.logits_scaling

    def _rows(self, tokens, last_idx=None):
        """Every layer over (B, S) tokens -> the stream x (B, S, d), the
        per-sequence state at `last_idx`, by kind of layer, and the routed
        layers' counts (None where there are none).  Positions past
        `last_idx` are given to no expert."""
        c = self.cfg
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        valid = None if last_idx is None or not c.n_experts \
            else positions <= last_idx[:, None]
        x = self._embed(tokens)
        ssm, kv, counts = [], [], None
        for layer in self.layers:
            if layer.kind == "mamba":
                x, state, mine = layer.mix(
                    x, lambda h: layer.mamba(h, last_idx), valid)
                ssm.append(state)
                counts = _sum_counts(counts, mine)
                continue
            attn = layer.attn

            def mixer(h):
                with jax.named_scope("attention"):
                    q, k, v = attn.project(h, positions)
                    o = causal_attention(q, k, v, c.attention_multiplier,
                                         c.attention)
                    return attn.combine(o), (k, v)

            x, cache, mine = layer.mix(x, mixer, valid)
            kv.append(cache)
            counts = _sum_counts(counts, mine)
        return x, {"ssm": ssm, "kv": kv}, counts

    def __call__(self, tokens):
        """Whole forward: (B, S) -> float32 logits (B, S, V)."""
        x, _, _ = self._rows(tokens)
        return self._head(x)

    def prefill(self, tokens, last_idx):
        """Right-padded rows (B, S) with each row's last token at
        `last_idx` -> float32 logits (B, V) at that token, and the state
        a decode continues from: {"ssm": [(conv window, S)] a Mamba
        layer, AT the row's last token; "kv": [(k, v)] an attention
        layer, (B, Hkv/2, S, 2 Dh) over the whole row, or (B, Hkv, S, Dh)
        where nothing is paired}; with routed experts also their counts
        (`EXPERT_COUNTS`) over the rows' real tokens."""
        x, state, counts = self._rows(tokens, last_idx)
        last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
        return (self._head(last), state) if counts is None \
            else (self._head(last), state, counts)

    def decode(self, token, pos, state, table, length, live=None):
        """One token a sequence: token (B,), `length` (B,) tokens already
        cached, `pos` (B,) their positions (read only where the
        configuration rotates) -> float32 logits (B, V) and the state
        with this token in it (and, with routed experts, the step's
        counts).  state: {"ssm"} as `prefill` gives it (batch first) and
        "pools": [(k_pool, v_pool)] an attention layer, (P, Hkv/2, page,
        2 Dh) or (P, Hkv, page, Dh), under `table` (B, NP).  A row where
        `live` is False keeps its Mamba state and is given to no expert
        (its pool writes land where its next live step writes again)."""
        from ray_tpu.ops.paged_attention import paged_decode_attention_batch

        c = self.cfg
        x = self._embed(token)[:, None]                     # (B, 1, d)
        ssm, pools, counts = [], [], None
        valid = None if live is None or not c.n_experts else live[:, None]

        def lift(f):        # a mixer over (B, d) as one over (B, 1, d)
            return lambda h: tuple(
                a[:, None] if j == 0 else a
                for j, a in enumerate(f(h[:, 0])))

        for layer in self.layers:
            if layer.kind == "mamba":
                prev = state["ssm"][len(ssm)]
                x, new, mine = layer.mix(x, lift(
                    lambda h: layer.mamba.step(h, prev, live)), valid)
                ssm.append(new)
                counts = _sum_counts(counts, mine)
                continue
            attn = layer.attn
            k_pool, v_pool = state["pools"][len(pools)]

            def mixer(h):
                # the kernel puts this token into the pool, in place,
                # before it reads it (float32 queries: the kernel
                # computes in float32 whatever they are)
                with jax.named_scope("attention"):
                    q, k, v = attn.project(h, pos[:, None])
                    o, kp, vp = paged_decode_attention_batch(
                        q[:, :, 0].astype(jnp.float32), k_pool, v_pool,
                        table, length + 1, k_new=k[:, :, 0],
                        v_new=v[:, :, 0], sm_scale=c.attention_multiplier)
                    return attn.combine(o[:, :, None]), (kp, vp)

            x, pool, mine = layer.mix(x, mixer, valid)
            pools.append(pool)
            counts = _sum_counts(counts, mine)
        new = {"ssm": ssm, "pools": pools}
        return (self._head(x[:, 0]), new) if counts is None \
            else (self._head(x[:, 0]), new, counts)


# ---------------------------------------------------------------------------
# Initialiser and counts
# ---------------------------------------------------------------------------

def init_params(cfg: GraniteHybridConfig, key, *, embed_std: float = 0.02,
                in_std: float = 0.02, qkv_std: float = 0.02,
                out_std: float = 0.02, final_norm: float = 1.0,
                step_size: tuple | None = None, decay: tuple | None = None,
                router_std: float = 0.02, expert_out_std: float = 0.02,
                ffn_out_std: float | None = None):
    """Seeded random weights: the embedding normal(0, `embed_std`); the
    matrices that read the stream normal(0, `in_std`), the attention
    layers' among them normal(0, `qkv_std`); those that write into the
    stream normal(0, `out_std`), the feed-forward's apart where
    `ffn_out_std` is given; of the routed experts W1 | W3 normal(0,
    `in_std`), W2 normal(0, `expert_out_std`) and the router normal(0,
    `router_std`); the final norm's scale `final_norm`, the
    other norms 1; Mamba-2's own parameters as their module draws them
    (the published initialiser: a head forgets in 1 / (dt |A|), some 1 to
    1,000 steps), unless `step_size` = (lo, hi) draws each head's dt
    log-uniformly between the two and `decay` = (lo, hi) its |A| likewise.
    (Which values a benchmark takes, and why, is the benchmark's:
    `benchmarks/families/granite_hybrid.py`.)"""
    model = GraniteHybridModel(cfg)
    drawn = model.init(key, jnp.zeros((1, 8), jnp.int32))
    flat = jax.tree_util.tree_flatten_with_path(drawn)[0]
    keys = jax.random.split(jax.random.fold_in(key, 1), len(flat))
    stds = {"embed": embed_std, "qkv_proj": qkv_std, "in_proj": in_std,
            "o_proj": out_std, "out_proj": out_std, "w13": in_std,
            "w2": expert_out_std, "router": router_std}
    out = []
    for (path, leaf), k in zip(flat, keys):
        names = [p.key for p in path]
        if names[-1] in ("kernel", "embedding") or names[-2] == "experts":
            std = stds[names[-1 if names[-2] == "experts" else -2]]
            if names[-3:-1] == ["mlp", "out_proj"] and ffn_out_std is not None:
                std = ffn_out_std
            leaf = (jax.random.normal(k, leaf.shape, jnp.float32) * std
                    ).astype(leaf.dtype)
        elif names[1:] == ["norm", "scale"]:
            leaf = leaf * final_norm
        elif names[-1] == "dt_bias" and step_size is not None:
            leaf = _dt_bias_init(k, leaf.shape, leaf.dtype, *step_size)
        elif names[-1] == "a_log" and decay is not None:
            lo, hi = map(math.log, decay)
            leaf = jax.random.uniform(k, leaf.shape, leaf.dtype, lo, hi)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(drawn),
                                        out)


def count_params(cfg: GraniteHybridConfig) -> dict:
    """Parameters by kind of layer (one layer of each, with the experts
    held HERE) and in all; one expert's and the router's beside them."""
    d, ff, E, H = cfg.d_model, cfg.d_ff, cfg.d_inner, cfg.mamba_heads
    expert, router = 3 * d * cfg.d_expert, d * cfg.n_experts
    # the shared feed-forward and the layer's two norms, then the routed
    mlp = 3 * d * ff + 2 * d + cfg.experts_here * expert + router
    one = {
        "mamba": mlp + d * (E + cfg.conv_dim + H)
        + cfg.d_conv * cfg.conv_dim + cfg.conv_dim + 3 * H + E + E * d,
        "attention": mlp + d * (cfg.n_heads + 2 * cfg.n_kv_heads)
        * cfg.head_dim + cfg.n_heads * cfg.head_dim * d,
    }
    total = sum(one[k] for k in cfg.layer_types) + cfg.vocab_size * d + d
    routed = dict(expert=expert, router=router) if cfg.n_experts else {}
    return dict(one, **routed, embedding=cfg.vocab_size * d, total=total)
