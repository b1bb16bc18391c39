"""LFM2-MoE decoder (`lfm2_moe`: LFM2-24B-A2B's architecture), TPU-first:
gated short convolutions with a few grouped-query attention layers among
them, and after every operator a feed-forward that is dense in the leading
layers and ROUTED in the rest: 64 experts, four a token, none dropped.

    h = E[token]
    for each layer i, by `layer_types[i]`:
        h += Op_i(RMSNorm(h))           short conv | attention
        h += FF_i(RMSNorm(h))           dense for i < n_dense_layers
    logits = RMSNorm(h) E^T             the embedding tied

Short conv (`ShortConv`): `[B | C | X] = W_in u`; the depthwise causal
conv of width L = 3 over B * X, no bias, no activation on its output;
`Op = W_out (C * conv)`.  A sequence's state is the last L - 1 columns of
B * X, taken AT the row's last token where the row is right-padded, and
kept in float32: 16 KB a layer and sequence, where rounding them to
bfloat16 put 1e-3 on the first router's input (my chip run, PR 42: a
leading layer's conv writes into a stream that holds little but the
embedding yet) and a router turns that into another expert.

Attention: 32 query and 8 KV heads of 64, an RMS norm with a learned scale
over the 64 of each q and k head BEFORE the rotary term (rotate-half,
theta 1e6), scores / sqrt(64), causal.  Heads of 64 run on the kernels'
heads of 128 as `models/granite_hybrid.py` pairs them: KV heads (2j, 2j+1)
lie as one head of 128 and a query head is padded with zeros on the half
it does not use.

Routed feed-forward (`route`, `expert_ffn`):

    s = sigmoid(W_g u)                          float32, 64 scores a token
    S = top-4 of (s + expert_bias)              the bias chooses ...
    g_e = s_e / (sum_{e' in S} s_e' + 1e-6)     ... and does not weigh
    FF = scaling * sum_{e in S} g_e W2_e (silu(W1_e u) * W3_e u)

Every token gets all of its experts: there is no capacity.  The step's
(row, expert) pairs are sorted by expert, and each of an expert's two
matrices (`w13` = [W1 | W3] side by side, and `w2`) is ONE grouped matrix
product over the sorted rows (`ops/grouped_matmul.py`: a Pallas kernel
that visits only the experts that hold a row and only the row tiles that
hold a pair, so a decode step of 16 rows streams the 40 experts its rows
chose and not 64, and a padded prompt pays for its real tokens).  A row
that is not `valid` (a slot held still, a position past a prompt's end)
is given to no expert: it is sorted past the last group, costs nothing
and gets zeros.

Precision (`models/sambay.py`'s `matmul` is this file's): parameters and
the K and V stored between steps are the configuration's `dtype`; the conv
windows, the router (its matrix, logits, sigmoid, top-k and gates), the
norms, the residual stream and softmax are float32.  An activation enters
every product with a weight as two bfloat16 terms, over a prompt as in a
decode step; the grouped products take the float32 rows and make the two
terms inside the kernel, a row tile at a time (`_two_terms`, handed to it).

What a step counted rides back with its logits (`expert_counts`: experts
touched, the slots they are counted against, the most rows one expert
took, the rows in all): `serve/llm_families.Lfm2MoeServing` names them for
the engine's spans.

Every part runs under a `jax.named_scope` (`short_conv`, `attention`,
`route`, `experts`, `mlp`, `head`), so a profile's operation names carry
them.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.llama import RMSNorm, apply_rope, rope_frequencies
from ray_tpu.models.sambay import (Linear, _two_terms, causal_attention,
                                   matmul)
from ray_tpu.ops.grouped_matmul import grouped_matmul

_PERIOD = ("full_attention", "conv", "conv", "conv")
# The published pattern: two convs, nine periods, a conv.
_PUBLISHED = ("conv", "conv") + _PERIOD * 9 + ("full_attention", "conv")


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    d_model: int = 2048
    layer_types: tuple = _PUBLISHED
    n_dense_layers: int = 2
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 11776              # the dense layers' feed-forward
    d_expert: int = 1536           # one expert's
    n_experts: int = 64
    top_k: int = 4
    conv_L: int = 3
    rope_theta: float = 1e6
    max_positions: int = 128000
    norm_eps: float = 1e-5
    routed_scaling: float = 1.0
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
    def n_expert_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    def layers_of(self, kind: str) -> list:
        return [i for i, k in enumerate(self.layer_types) if k == kind]


LFM2_24B_A2B = Lfm2MoeConfig()
TINY_LFM2_MOE = Lfm2MoeConfig(
    vocab_size=256, d_model=64, n_dense_layers=1,
    layer_types=("conv",) + ("full_attention", "conv", "conv") * 2,
    n_heads=4, n_kv_heads=2, d_ff=128, d_expert=32, n_experts=8, top_k=2,
    max_positions=256, dtype=jnp.float32, attention="reference")


# ---------------------------------------------------------------------------
# The routed feed-forward
# ---------------------------------------------------------------------------


def router_logits(u, w):
    """u (T, d) float32 against the router's float32 matrix (d, E), at the
    highest precision: 64 columns, nothing beside the experts' own."""
    return jnp.dot(u, w, precision=jax.lax.Precision.HIGHEST)


def route(logits, bias, top_k: int, eps: float = 1e-6):
    """Router logits (T, E) float32 and the selection bias (E,) -> the
    chosen experts (T, k) and their gates (T, k), float32: sigmoid scores,
    the k largest of score + bias, the UNBIASED scores of the chosen
    renormalised to sum to one (over their sum + `eps`: each family's
    published constant)."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + eps)


def expert_ffn(u, idx, gates, w13, w2, valid=None, first=None):
    """u (T, d) float32; idx, gates (T, k); w13 (E, d, 2 f) = [W1 | W3];
    w2 (E, f, d); valid (T,) bool or None -> (sum over a row's experts of
    g W2 (silu(W1 u) * W3 u), (T, d) float32, zeros where not valid;
    `expert_counts` of the call).  The (row, expert) pairs are sorted by
    expert and each matrix is one grouped product over their float32 rows
    (where the matrices are bfloat16 the kernel makes a row's two terms
    itself); every pair of a valid row is computed, whatever the routing.
    The kernel moves the rows: the first product reads each sorted pair's
    row of u by its id and stores `silu(a) * b`, the second writes each
    pair's result to its place in pair order, and nothing else touches a
    (T k, d) array but the gated sum (`ops/grouped_matmul.py`, PR 54).

    `first`: the matrices are a chip's SHARE of a layer's experts, those
    numbered `first` ... `first + E - 1` of the router's (None: all of
    them, from 0).  `idx` and `gates` are then still the router's whole
    choice; a pair whose expert is held elsewhere goes the way of a row
    that is not valid (no group, no visit, zeros), and what comes back is
    this share's PART of the routed sum, for whoever adds the parts."""
    T, k = idx.shape
    E = w13.shape[0]
    # pair p = j T + t is row t's j-th expert: (k, T, d) is then (k T, d)
    # as it lies, where (T, k, d) pads k to the float32 tile's 8 rows and
    # is a copy of every pair's result (a `reshape` line of a profile)
    flat = idx.T.reshape(-1)
    kept = gates
    if first is not None:
        # held elsewhere: no expert here (E), and no gate
        here = (idx >= first) & (idx < first + E)
        flat = jnp.where(here.T.reshape(-1), flat - first, E)
        kept = jnp.where(here, kept, 0.0)
    if valid is not None:
        # no expert: sorted past the last group
        flat = jnp.where(jnp.tile(valid, k), flat, E)
        kept = jnp.where(valid[:, None], kept, 0.0)
    order = jnp.argsort(flat)                   # stable: pairs by expert
    sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1, mode="drop")
    # the kernel moves the rows: sorted pair i's row of u comes in by its
    # id, and its result goes out to row `order[i]`: pair order, each row
    # in the parts it was copied in, which the gated sum reads as they lie
    h = grouped_matmul(u, w13, sizes, _two_terms, rows=order % T, gated=True)
    y = grouped_matmul(h, w2, sizes, _two_terms, to=order)
    y = y.reshape(k, T, *y.shape[1:])
    # a pair of no group was never written and holds anything: dropped
    kept = kept.T[..., None, None]
    out = jnp.sum(jnp.where(kept > 0, y, 0.0) * kept, axis=0).reshape(T, -1)
    return out, expert_counts(sizes)


# What a routed layer counts of one call, in this order.
EXPERT_COUNTS = ("experts_touched", "expert_slots", "expert_rows_max",
                 "expert_rows")


def expert_counts(sizes):
    """(4,) int32 of one layer's group sizes: experts that took a row,
    experts there are, the most rows one took, the rows in all."""
    return jnp.stack([jnp.sum(sizes > 0), jnp.int32(sizes.shape[0]),
                      jnp.max(sizes), jnp.sum(sizes)]).astype(jnp.int32)


def add_counts(a, b):
    """Two layers' counts as one: sums, but the largest group's rows."""
    return jnp.stack([a[0] + b[0], a[1] + b[1], jnp.maximum(a[2], b[2]),
                      a[3] + b[3]])


class RoutedExperts(nn.Module):
    """`cfg`: any configuration with `n_experts`, `top_k`, `d_model`,
    `d_expert`, `routed_scaling` and `dtype` (`models/mla_moe.py`'s and
    `models/granite_hybrid.py`'s too); `eps`: what the family adds to the
    chosen scores' sum, where that is not `route`'s own; `choose`: a
    family's own router, `choose(logits, top_k)` -> (idx, gates), in
    `route`'s place (there is then no selection bias); `held` = (first,
    count): the experts this chip holds of the router's `n_experts`, where
    a layer's experts are divided over chips (None: all).  The router and
    its choice are always the whole layer's; the output is the held
    experts' part of the routed sum (`expert_ffn`)."""
    cfg: Any
    # (None and not 1e-6: `route` is then called with three arguments, as
    # the planted routes of `benchmarks/tools/lfm2_moe_faults.py` take it)
    eps: float | None = None
    choose: Any = None
    held: tuple | None = None

    def setup(self):
        c = self.cfg
        E, d, f = c.n_experts, c.d_model, c.d_expert
        first, count = self.held or (0, E)
        if not 0 <= first <= first + count <= E or not count:
            raise ValueError(f"held={self.held}: a run of the router's "
                             f"{E} experts")
        init = nn.initializers.normal(0.02)
        # the router in float32 (0.5 MB a layer at the published sizes)
        self.router = self.param("router", init, (d, E), jnp.float32)
        if self.choose is None:
            self.expert_bias = self.param(
                "expert_bias", nn.initializers.zeros, (E,), jnp.float32)
        self.w13 = self.param("w13", init, (count, d, 2 * f), c.dtype)
        self.w2 = self.param("w2", init, (count, f, d), c.dtype)

    def __call__(self, u, valid=None):
        """u (..., d) float32 -> (the layer's output, its counts)."""
        c = self.cfg
        flat = u.reshape(-1, c.d_model)
        with jax.named_scope("route"):
            logits = router_logits(flat, self.router)
            idx, gates = self.choose(logits, c.top_k) \
                if self.choose is not None else route(
                    logits, self.expert_bias, c.top_k,
                    **({} if self.eps is None else {"eps": self.eps}))
        with jax.named_scope("experts"):
            out, counts = expert_ffn(
                flat, idx, gates, self.w13, self.w2,
                None if valid is None else valid.reshape(-1),
                **({} if self.held is None else {"first": self.held[0]}))
        return (c.routed_scaling * out).reshape(u.shape), counts


class MLP(nn.Module):
    """`W2 (silu(W1 u) * W3 u)`, W1 and W3 side by side."""
    cfg: Lfm2MoeConfig

    def setup(self):
        c = self.cfg
        self.w13 = Linear(2 * c.d_ff, c.dtype)
        self.w2 = Linear(c.d_model, c.dtype)

    def __call__(self, u):
        with jax.named_scope("mlp"):
            a, b = jnp.split(self.w13(u, precise=True), 2, axis=-1)
            return self.w2(nn.silu(a) * b, precise=True)


# ---------------------------------------------------------------------------
# The gated short convolution
# ---------------------------------------------------------------------------


class ShortConv(nn.Module):
    """State of one sequence: the last L - 1 columns of B * X,
    (L - 1, d) float32."""
    cfg: Lfm2MoeConfig

    def setup(self):
        c = self.cfg
        self.in_proj = Linear(3 * c.d_model, c.dtype)
        self.conv_w = self.param("conv_w", nn.initializers.normal(0.2),
                                 (c.conv_L, c.d_model), c.dtype)
        self.out_proj = Linear(c.d_model, c.dtype)

    def _gates(self, u):
        b, c_gate, x = jnp.split(self.in_proj(u, precise=True), 3, axis=-1)
        return b * x, c_gate

    def __call__(self, u, last_idx=None):
        """u (B, S, d) -> (out, window): the window after each row's
        `last_idx` (after its last position where None)."""
        c = self.cfg
        B, S, _ = u.shape
        L = c.conv_L
        f32 = lambda v: v.astype(jnp.float32)  # noqa: E731
        bx, c_gate = self._gates(u)
        with jax.named_scope("short_conv"):
            padded = jnp.pad(bx, ((0, 0), (L - 1, 0), (0, 0)))
            v = sum(padded[:, j: j + S] * f32(self.conv_w[j])
                    for j in range(L))
            if last_idx is None:
                last_idx = jnp.full((B,), S - 1, jnp.int32)
            # padded position p holds input p - (L - 1): the L - 1 inputs
            # that end at last_idx are padded positions last_idx + 1 ...
            window = jnp.take_along_axis(
                padded, (last_idx[:, None] + 1 + jnp.arange(L - 1))[
                    :, :, None], axis=1)
        return self.out_proj(c_gate * v, precise=True), window

    def step(self, u, window, live=None):
        """One token: u (B, d), window (B, L - 1, d) -> (out, new window).
        A row where `live` is False keeps its window."""
        bx, c_gate = self._gates(u)
        with jax.named_scope("short_conv"):
            seen = jnp.concatenate([window, bx[:, None]], axis=1)
            v = jnp.sum(seen * self.conv_w.astype(jnp.float32), axis=1)
            new = seen[:, 1:]
            if live is not None:
                new = jnp.where(live[:, None, None], new, window)
        return self.out_proj(c_gate * v, precise=True), new


# ---------------------------------------------------------------------------
# Attention: heads of 64 as halves of heads of 128, q and k normed a head
# ---------------------------------------------------------------------------


class Attention(nn.Module):
    cfg: Lfm2MoeConfig

    def setup(self):
        c = self.cfg
        if c.n_kv_heads % 2 or c.n_heads % c.n_kv_heads:
            raise ValueError("KV heads pair up, and whole groups of query "
                             "heads share a KV head")
        self.qkv_proj = Linear((c.n_heads + 2 * c.n_kv_heads) * c.head_dim,
                               c.dtype)
        self.q_norm = RMSNorm(c.norm_eps)
        self.k_norm = RMSNorm(c.norm_eps)
        self.o_proj = Linear(c.d_model, c.dtype)

    @property
    def sm_scale(self) -> float:
        return 1.0 / math.sqrt(self.cfg.head_dim)

    def project(self, h, positions):
        """h (B, S, d), positions (B, S) -> q' (B, Hq, S, 2 Dh) float32,
        zero on the half it does not use; K' and V' (B, Hkv/2, S, 2 Dh)
        in the type a cache holds them in."""
        c = self.cfg
        B, S, _ = h.shape
        Dh, Hq, Hkv = c.head_dim, c.n_heads, c.n_kv_heads
        q, k, v = jnp.split(self.qkv_proj(h, precise=True),
                            [Hq * Dh, (Hq + Hkv) * Dh], axis=-1)
        cos, sin = rope_frequencies(Dh, c.max_positions, c.rope_theta)
        rot = lambda a: apply_rope(  # noqa: E731
            a.transpose(0, 2, 1, 3), cos, sin, positions).transpose(
                0, 2, 1, 3)
        q = rot(self.q_norm(q.reshape(B, S, Hq, Dh)))
        k = rot(self.k_norm(k.reshape(B, S, Hkv, Dh)))
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
        [v_2j | v_2j+1] -> its own half -> the layer's output (B, S, d)."""
        c = self.cfg
        B, Hq, S, D2 = attn.shape
        Dh, G = c.head_dim, c.n_heads // c.n_kv_heads
        a = attn.reshape(B, c.n_kv_heads // 2, 2, G, S, 2, Dh)
        o = jnp.stack([a[:, :, 0, :, :, 0], a[:, :, 1, :, :, 1]], axis=2)
        o = o.reshape(B, Hq, S, Dh).transpose(0, 2, 1, 3)
        return self.o_proj(o.reshape(B, S, c.d_model), precise=True)


# ---------------------------------------------------------------------------
# The stack
# ---------------------------------------------------------------------------


class Layer(nn.Module):
    """One decoder block: the operator `layer_types` names, then the
    feed-forward, dense or routed."""
    cfg: Lfm2MoeConfig
    kind: str
    routed: bool

    def setup(self):
        c = self.cfg
        self.operator_norm = RMSNorm(c.norm_eps)
        self.ffn_norm = RMSNorm(c.norm_eps)
        if self.kind == "conv":
            self.conv = ShortConv(c)
        elif self.kind == "full_attention":
            self.attn = Attention(c)
        else:
            raise ValueError(f"layer_types holds {self.kind!r}: a layer is "
                             "'conv' or 'full_attention'")
        if self.routed:
            self.experts = RoutedExperts(c)
        else:
            self.mlp = MLP(c)

    def mix(self, x, operator, counts, valid=None):
        """x += operator(norm(x)); x += FF(norm(x)), the stream in
        float32 -> (x, what else the operator returns, the counts with
        this layer's in them)."""
        out, rest = operator(self.operator_norm(x))
        x = x + out
        u = self.ffn_norm(x)
        if not self.routed:
            return x + self.mlp(u), rest, counts
        out, mine = self.experts(u, valid)
        return x + out, rest, mine if counts is None \
            else add_counts(counts, mine)


class Lfm2MoeModel(nn.Module):
    cfg: Lfm2MoeConfig

    def setup(self):
        c = self.cfg
        self.embed = nn.Embed(c.vocab_size, c.d_model, dtype=c.dtype,
                              param_dtype=c.dtype)
        self.layers = [Layer(c, kind, i >= c.n_dense_layers)
                       for i, kind in enumerate(c.layer_types)]
        self.norm = RMSNorm(c.norm_eps)

    def _head(self, x):
        with jax.named_scope("head"):
            return matmul(self.norm(x), self.embed.embedding.T, True)

    def _rows(self, tokens, last_idx=None):
        """Every layer over (B, S) tokens -> the stream x (B, S, d), the
        per-sequence state at `last_idx` by kind of layer, the routed
        layers' counts.  Positions past `last_idx` are given to no
        expert."""
        c = self.cfg
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        valid = None if last_idx is None \
            else positions <= last_idx[:, None]
        x = self.embed(tokens).astype(jnp.float32)
        conv, kv, counts = [], [], None
        for layer in self.layers:
            if layer.kind == "conv":
                x, window, counts = layer.mix(
                    x, lambda u: layer.conv(u, last_idx), counts, valid)
                conv.append(window)
                continue
            attn = layer.attn

            def operator(u):
                with jax.named_scope("attention"):
                    q, k, v = attn.project(u, positions)
                    o = causal_attention(q, k, v, attn.sm_scale,
                                         c.attention)
                    return attn.combine(o), (k, v)

            x, cache, counts = layer.mix(x, operator, counts, valid)
            kv.append(cache)
        return x, {"conv": conv, "kv": kv}, counts

    def __call__(self, tokens):
        """Whole forward: (B, S) -> float32 logits (B, S, V)."""
        x, _, _ = self._rows(tokens)
        return self._head(x)

    def prefill(self, tokens, last_idx):
        """Right-padded rows (B, S) with each row's last token at
        `last_idx` -> float32 logits (B, V) at that token; the state a
        decode continues from: {"conv": [window] a conv layer, AT the
        row's last token; "kv": [(k, v)] an attention layer, (B, Hkv/2,
        S, 2 Dh) over the whole row}; and the routed layers' counts
        (`EXPERT_COUNTS`) over the rows' real tokens."""
        x, state, counts = self._rows(tokens, last_idx)
        last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
        return self._head(last), state, counts

    def decode(self, token, pos, state, table, length, live=None):
        """One token a sequence: token (B,), `length` (B,) tokens already
        cached at positions `pos` (B,) -> float32 logits (B, V), the state
        with this token in it, and the step's counts.  state: {"conv"} as
        `prefill` gives it (batch first) and "pools": [(k_pool, v_pool)]
        an attention layer, (P, Hkv/2, page, 2 Dh) under `table` (B, NP).
        A row where `live` is False keeps its windows and is given to no
        expert (its pool writes land where its next live step writes
        again)."""
        from ray_tpu.ops.paged_attention import paged_decode_attention_batch

        x = self.embed(token).astype(jnp.float32)[:, None]  # (B, 1, d)
        conv, pools, counts = [], [], None
        valid = None if live is None else live[:, None]

        def lift(f):        # an operator over (B, d) as one over (B, 1, d)
            def lifted(u):
                out, rest = f(u[:, 0])
                return out[:, None], rest
            return lifted

        for layer in self.layers:
            if layer.kind == "conv":
                prev = state["conv"][len(conv)]
                x, new, counts = layer.mix(x, lift(
                    lambda u: layer.conv.step(u, prev, live)), counts, valid)
                conv.append(new)
                continue
            attn = layer.attn
            k_pool, v_pool = state["pools"][len(pools)]

            def operator(u):
                # the kernel puts this token into the pool, in place,
                # before it reads it
                with jax.named_scope("attention"):
                    q, k, v = attn.project(u, pos[:, None])
                    o, kp, vp = paged_decode_attention_batch(
                        q[:, :, 0], k_pool, v_pool, table, length + 1,
                        k_new=k[:, :, 0], v_new=v[:, :, 0],
                        sm_scale=attn.sm_scale)
                    return attn.combine(o[:, :, None]), (kp, vp)

            x, pool, counts = layer.mix(x, operator, counts, valid)
            pools.append(pool)
        return self._head(x[:, 0]), {"conv": conv, "pools": pools}, counts


# ---------------------------------------------------------------------------
# Initialiser and counts
# ---------------------------------------------------------------------------

def init_params(cfg: Lfm2MoeConfig, key, *, embed_std: float = 0.02,
                in_std: float = 0.02, qkv_std: float = 0.02,
                out_std: float = 0.02, ffn_out_std: float = 0.02,
                expert_out_std: float = 0.02, router_std: float = 0.02,
                bias_std: float = 0.0, final_norm: float = 1.0):
    """Seeded random weights: the embedding normal(0, `embed_std`); the
    matrices that read the stream (the conv's in-projection, W1 | W3 of
    the dense feed-forward and of every expert) normal(0, `in_std`), q, k
    and v normal(0, `qkv_std`); those that write into it: the operators'
    (the conv's and the attention's out-projections) normal(0, `out_std`),
    the dense feed-forward's W2 normal(0, `ffn_out_std`), every expert's
    normal(0, `expert_out_std`); the router normal(0, `router_std`) and
    the selection bias normal(0, `bias_std`) (zero, as a fresh buffer is,
    at 0); the final norm's scale `final_norm`, the other norms 1; the
    conv's taps as their module draws them.  (Which values a benchmark
    takes, and why, is the benchmark's: `benchmarks/families/lfm2_moe.py`.)"""
    drawn = Lfm2MoeModel(cfg).init(key, jnp.zeros((1, 8), jnp.int32))
    stds = {"embed": embed_std, "qkv_proj": qkv_std, "in_proj": in_std,
            "w13": in_std, "o_proj": out_std, "out_proj": out_std,
            "mlp/w2": ffn_out_std, "experts/w2": expert_out_std,
            "router": router_std, "expert_bias": bias_std}
    return draw_named(
        drawn, key, stds,
        lambda names, leaf: leaf * final_norm
        if names[1:] == ["norm", "scale"] else leaf)


def draw_named(drawn, key, stds: dict, other=lambda names, leaf: leaf):
    """The parameter tree `drawn` with every leaf that `stds` names (by
    its module's name, or by the last two names of its path) drawn anew
    from normal(0, its std), a key a leaf in the tree's order; the rest
    through `other(names, leaf)`."""
    flat = jax.tree_util.tree_flatten_with_path(drawn)[0]
    keys = jax.random.split(jax.random.fold_in(key, 1), len(flat))
    out = []
    for (path, leaf), k in zip(flat, keys):
        names = [p.key for p in path]
        if names[-1] in ("kernel", "embedding"):
            names.pop()
        name = names[-1] if names[-1] in stds else "/".join(names[-2:])
        if name in stds:
            leaf = (jax.random.normal(k, leaf.shape, jnp.float32)
                    * stds[name]).astype(leaf.dtype)
        else:
            leaf = other(names, leaf)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(drawn),
                                        out)


def count_params(cfg: Lfm2MoeConfig) -> dict:
    """Parameters by part (one of each) and in all."""
    d, Dh = cfg.d_model, cfg.head_dim
    one = {
        "conv": 3 * d * d + d * d + cfg.conv_L * d,
        "full_attention": d * (cfg.n_heads + 2 * cfg.n_kv_heads) * Dh
        + cfg.n_heads * Dh * d + 2 * Dh,
        "dense_ffn": 3 * d * cfg.d_ff,
        "expert": 3 * d * cfg.d_expert,
        "router": d * cfg.n_experts + cfg.n_experts,
    }
    routed = cfg.n_experts * one["expert"] + one["router"]
    total = sum(one[k] + 2 * d for k in cfg.layer_types) \
        + cfg.n_dense_layers * one["dense_ffn"] \
        + cfg.n_expert_layers * routed + cfg.vocab_size * d + d
    return dict(one, routed_layer=routed, embedding=cfg.vocab_size * d,
                total=total)
