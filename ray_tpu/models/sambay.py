"""SambaY decoder (Phi-4-mini-flash-reasoning's architecture), TPU-first.

A decoder-decoder hybrid (Ren et al. 2025, "Decoder-Hybrid-Decoder
Architecture for Efficient Reasoning with Long Generation"): the first
half of the stack, the SELF-decoder, alternates Mamba-1 layers with
differential attention over a sliding window and ends in one Mamba layer
and one layer of full causal attention; the second half, the
CROSS-decoder, alternates Gated Memory Units, which gate the last Mamba
layer's scan output at the same position, with differential
cross-attention over the ONE key-value cache the full-attention layer
wrote (YOCO).  No rotary and no position embedding of any kind: the
state-space layers carry position.  With L layers (L = 32 published,
L/2 = 16):

    i even, i <= L/2   Mamba-1; layer L/2 also hands its scan output
                       y_t (before the gate by z) down as the memory m_t
    i odd,  i <  L/2   differential attention, window of `window` keys
    i = L/2 + 1        differential attention, full causal; its K and V
                       are the model's key-value cache
    i even, i > L/2+1  GMU(h, m) = (m * silu(h W_1)) W_2
    i odd,  i > L/2+1  differential cross-attention: own W_q, W_o only

and every layer is x += Mix_i(LN(x)); x += MLP(LN(x)).

So a served sequence has THREE kinds of state (`decode` advances them,
`serve/llm_families.py` tells the engine): pages of one layer's K/V, read
by every cross layer; a ring of the last `window` tokens' K/V for each
window layer; and (conv window, scan state) for each Mamba layer.  A
prompt's prefill does less than a forward pass, exactly: a cross-decoder
layer at a prompt position feeds nothing but that position's own logits,
and of the full-attention layer nothing but its K and V is read at another
position.  And it is computed a BLOCK of positions at a time, by two
programs that the family's class dispatches from the host
(`SambaYServing.prefill_from_host`): `prompt_block` runs layers 0 .. L/2
and the full layer's K/V projection over one block after the state the
block before left (the same three kinds of state, and the stream and the
memory at each row's last token once it has passed), as many times as the
longest prompt has blocks; `prompt_tail` runs the rest of the full layer
and the cross-decoder at each row's last token only: one query a row.  No
program loops over blocks, and what lies past a prompt's last block in
its bucket is not computed.

Differential attention (Ye et al. 2024) on ordinary attention kernels.
Heads of 64 pair up, (2p, 2p+1) -> pair p; a pair's output is
(softmax(q1 k1') - lambda softmax(q2 k2')) [v1; v2].  Here a KV pair is
ONE head of 128, K = [k1 | k2] and V = [v1; v2], and a query head is
padded with zeros to 128 on the half it does not use ([q1 | 0], [0 | q2]),
so that q' . K = q . k exactly: attention is then plain grouped-query
attention with heads of 128, four query heads a KV head, scale 1/8, and
the flash and paged kernels of `ray_tpu/ops` run it unchanged.  The cache
holds 2 x 10 x 128 values a token, what 20 KV heads of 64 hold.

Precision.  Parameters, matrix-product operands and everything stored
between steps (K, V, conv windows) are the configuration's `dtype`
(bfloat16 as served); the residual stream, the norms, softmax and the
scan state are float32.  Where few rows run (`precise=True`: every
decode step, and the cross-decoder at a prompt's last token) an
activation enters a product as TWO bfloat16 terms, its leading bits and
what they left, stacked as extra rows of ONE product against the same
weights (`_two_terms`): the weights stream once, the product is exact to
2^-16, and at 32 rows the extra rows cost nothing a memory-bound step
can feel.  Over a whole prompt (compute-bound) an activation is rounded
once, as any bfloat16 matmul rounds it.  Why: measured on the v5e at
published widths against the float32 reference (PERF.md, PR 28), 32
layers of products whose operands are all rounded put a noise of 0.065
(standard deviation) on the difference of two logits of a vocabulary of
200,064, and a greedy token then lies 0.125 or more under the
reference's best at one position in 200; with two-term products in
decode the widest such gap in 40 requests (27,000 positions) was 0.089.

Conventions otherwise as in llama.py: activations (batch, seq, d_model);
the scan state is (N, E) with E minor (`models/ssm.py`).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.ssm import chunked_selective_scan
from ray_tpu.ops.attention import flash_attention

_NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class SambaYConfig:
    vocab_size: int = 200064
    d_model: int = 2560
    n_layers: int = 32
    n_heads: int = 40          # query heads of head_dim (pairs: n_heads / 2)
    n_kv_heads: int = 20
    d_ff: int = 10240
    window: int = 512
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # the full layer over whole rows (`__call__`): "flash" (pallas) or
    # "reference" (plain jnp)
    attention: str = "flash"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return -(-self.d_model // 16)

    @property
    def kv_pairs(self) -> int:
        return self.n_kv_heads // 2

    @property
    def n_self(self) -> int:
        """Layers of the self-decoder: all that run over a whole prompt."""
        return self.n_layers // 2 + 2

    def kind(self, i: int) -> str:
        half = self.n_layers // 2
        if i <= half:
            return "window" if i % 2 else "mamba"
        if i == half + 1:
            return "full"
        return "cross" if i % 2 else "gmu"

    def layers_of(self, kind: str) -> list:
        return [i for i in range(self.n_layers) if self.kind(i) == kind]

    def lambda_init(self, i: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * i)


PHI4_MINI_FLASH = SambaYConfig()
TINY_SAMBAY = SambaYConfig(vocab_size=256, d_model=64, n_layers=8, n_heads=4,
                           n_kv_heads=2, d_ff=128, window=8, d_state=4,
                           dtype=jnp.float32, attention="reference")


class LayerNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],))
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        return (xf - mean) * jax.lax.rsqrt(var + self.eps) * scale + bias


def _two_terms(a, axis: int):
    """`a` as two bfloat16 terms whose sum is `a` to 2^-16, stacked along
    `axis`: a product with them is one product with twice the rows.  The
    first term is `a` with the low 16 bits of its float32 form cleared (a
    bfloat16 value, exactly), the second what that left.  (Not `a` rounded
    to bfloat16 and back: the compiler is allowed to keep the excess
    precision of such a round trip, and then the second term is zero; my
    chip run, PR 28: a product of "two terms" made so was bit for bit the
    plain one.)"""
    a = a.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return jnp.concatenate([hi.astype(jnp.bfloat16),
                            (a - hi).astype(jnp.bfloat16)], axis=axis)


def _halves(out, axis: int):
    a, b = jnp.split(out, 2, axis=axis)
    return a + b


def matmul(x, w, precise: bool = False):
    """x (rows..., d) @ w (d, n), products accumulated in float32.  A
    float32 model: plain.  Else x is rounded to w's type (`precise`
    False: the product comes back in that type too) or enters as two
    terms (`precise`: float32 out)."""
    if w.dtype == jnp.float32:
        return jnp.dot(x.astype(jnp.float32), w)
    if not precise:
        return jnp.dot(x.astype(w.dtype), w)
    return _halves(jnp.dot(_two_terms(x, 0), w,
                           preferred_element_type=jnp.float32), 0)


class Linear(nn.Module):
    """A projection whose `kernel` (and `bias`) are named as `nn.Dense`
    names them, multiplied by `matmul`."""
    features: int
    dtype: Any
    use_bias: bool = False
    bias_init: Any = nn.initializers.zeros

    @nn.compact
    def __call__(self, x, precise: bool = False, columns=None):
        """`columns` (first, end): that slice of the kernel's columns alone
        (a projection that is several side by side; no bias then)."""
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features), self.dtype)
        if columns is not None:
            return matmul(x, kernel[:, columns[0]:columns[1]], precise)
        out = matmul(x, kernel, precise)
        if self.use_bias:
            out = out + self.param("bias", self.bias_init,
                                   (self.features,), self.dtype)
        return out


class MLP(nn.Module):
    """`llama.MLP`'s feed-forward under its parameter names, through
    `matmul` (llama's rounds every operand; this one need not)."""
    cfg: Any

    def setup(self):
        c = self.cfg
        self.gate_proj = Linear(c.d_ff, c.dtype)
        self.up_proj = Linear(c.d_ff, c.dtype)
        self.down_proj = Linear(c.d_model, c.dtype)

    def __call__(self, x, precise: bool = False):
        return self.down_proj(
            nn.silu(self.gate_proj(x, precise)) * self.up_proj(x, precise),
            precise)


# ---------------------------------------------------------------------------
# Attention over heads of 2 * head_dim (see the module's head)
# ---------------------------------------------------------------------------


def _grouped(q, k):
    """q (B, Hq, S, D) -> (B, Hkv, G, S, D) beside k (B, Hkv, T, D)."""
    B, Hq, S, D = q.shape
    return q.reshape(B, k.shape[1], Hq // k.shape[1], S, D)


def masked_attention(q, k, v, mask, sm_scale: float, precise: bool = False):
    """Plain grouped-query attention.  q (B, Hq, S, D); k, v (B, Hkv, T, D);
    mask broadcastable to (B, 1, 1, S, T), True where a key is seen.
    Float32 out: a pair's two outputs are subtracted next, and what
    cancels there should not have been rounded first.  `precise`: the
    queries and the weights of the softmax enter as two terms (group
    rows), as `matmul`'s activations do."""
    qg = _grouped(q, k)
    two = precise and k.dtype != jnp.float32
    f32 = dict(preferred_element_type=jnp.float32)
    qg = _two_terms(qg, 2) if two else qg.astype(k.dtype)
    s = jnp.einsum("bhgsd,bhtd->bhgst", qg, k, **f32)
    s = (_halves(s, 2) if two else s) * sm_scale
    p = jax.nn.softmax(jnp.where(mask, s, _NEG_INF), axis=-1)
    p = _two_terms(p, 2) if two else p.astype(v.dtype)
    out = jnp.einsum("bhgst,bhtd->bhgsd", p, v, **f32)
    return (_halves(out, 2) if two else out).reshape(q.shape)


def window_attention(q, k, v, window: int, sm_scale: float,
                     precise: bool = False, before=None, start=0):
    """Causal attention in which a query sees the last `window` keys, its
    own among them.  Blocks of `window` queries against their own and the
    previous block of keys, a block at a time (`lax.map`), so the scores
    in flight are (B, Hq, window, 2 * window) whatever the length.
    `before` (k, v) (B, Hkv, window, D): the `window` keys and values that
    precede these positions, which begin at `start` (a multiple of
    `window`, may be traced; at 0 nothing precedes and `before` is not
    read)."""
    B, Hq, S, D = q.shape
    nb = -(-S // window)
    pad = nb * window - S
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for a in (q, k, v))

    def blocks(a):      # (B, H, nb * window, D) -> (nb, B, H, window, D)
        return a.reshape(B, a.shape[1], nb, window, D).transpose(2, 0, 1, 3, 4)

    def with_previous(a, first):
        first = jnp.zeros_like(a[:1]) if first is None \
            else first.astype(a.dtype)[None]
        prev = jnp.concatenate([first, a[:-1]], axis=0)
        return jnp.concatenate([prev, a], axis=3)   # (nb, B, H, 2w, D)

    qpos = jnp.arange(window)[:, None] + window
    kpos = jnp.arange(2 * window)[None, :]
    seen = (kpos <= qpos) & (kpos > qpos - window)
    kb, vb = before if before is not None else (None, None)

    def one(args):
        blk, qb, kb, vb = args
        # the first block's "previous" keys are padding, not tokens
        mask = seen & ((blk > 0) | (start > 0) | (kpos >= window))
        return masked_attention(qb, kb, vb, mask, sm_scale, precise)

    with jax.named_scope("window_attention"):
        out = jax.lax.map(one, (jnp.arange(nb), blocks(q),
                                with_previous(blocks(k), kb),
                                with_previous(blocks(v), vb)))
    out = out.transpose(1, 2, 0, 3, 4).reshape(B, Hq, nb * window, D)
    return out[:, :, :S]


def causal_attention(q, k, v, sm_scale: float, impl: str,
                     precise: bool = False):
    if impl == "flash" and not precise:
        rep = q.shape[1] // k.shape[1]
        return flash_attention(q.astype(k.dtype), jnp.repeat(k, rep, axis=1),
                               jnp.repeat(v, rep, axis=1), sm_scale, True)
    S = q.shape[2]
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    return masked_attention(q, k, v, mask, sm_scale, precise)


class DiffAttention(nn.Module):
    """Differential attention of one layer; `cross` layers have W_q and
    W_o only and attend over keys and values handed in."""
    cfg: SambaYConfig
    layer: int
    cross: bool = False

    def setup(self):
        c = self.cfg
        Dh = c.head_dim
        if self.cross:
            self.q_proj = Linear(c.n_heads * Dh, c.dtype)
        else:
            self.qkv_proj = Linear((c.n_heads + 2 * c.n_kv_heads) * Dh,
                                   c.dtype)
        self.o_proj = Linear(c.d_model, c.dtype)
        lam = nn.initializers.normal(0.1)
        self.lambda_q1 = self.param("lambda_q1", lam, (Dh,), jnp.float32)
        self.lambda_k1 = self.param("lambda_k1", lam, (Dh,), jnp.float32)
        self.lambda_q2 = self.param("lambda_q2", lam, (Dh,), jnp.float32)
        self.lambda_k2 = self.param("lambda_k2", lam, (Dh,), jnp.float32)
        self.subln = self.param("subln", nn.initializers.ones, (2 * Dh,),
                                jnp.float32)

    @property
    def sm_scale(self) -> float:
        return 1.0 / math.sqrt(self.cfg.head_dim)

    def project(self, h, precise: bool = False):
        """h (B, S, d) -> q' (B, Hq, S, 2 Dh) zero-padded on the unused
        half, and for a self-attention layer K, V (B, Hkv/2, S, 2 Dh) in
        the type a cache holds them in."""
        c = self.cfg
        if self.cross:
            return self._padded(self.q_proj(h, precise)), None, None
        q, k, v = jnp.split(
            self.qkv_proj(h, precise),
            [c.n_heads * c.head_dim, (c.n_heads + c.n_kv_heads) * c.head_dim],
            axis=-1)
        return self._padded(q), self._pairs(k), self._pairs(v)

    def queries(self, h, precise: bool = False):
        """A self-attention layer's q' alone: the projection's first
        columns."""
        c = self.cfg
        return self._padded(self.qkv_proj(
            h, precise, columns=(0, c.n_heads * c.head_dim)))

    def keys_values(self, h, precise: bool = False):
        """... and its K, V alone: the rest."""
        c = self.cfg
        first = c.n_heads * c.head_dim
        k, v = jnp.split(self.qkv_proj(
            h, precise, columns=(first, first + 2 * c.n_kv_heads
                                 * c.head_dim)), 2, axis=-1)
        return self._pairs(k), self._pairs(v)

    def _pairs(self, a):
        c = self.cfg
        return a.astype(c.dtype).reshape(
            *a.shape[:2], c.n_kv_heads // 2, 2 * c.head_dim).transpose(
                0, 2, 1, 3)

    def _padded(self, q):
        c = self.cfg
        B, S, _ = q.shape
        Dh, Hq = c.head_dim, c.n_heads
        q = q.reshape(B, S, Hq // 2, 2, Dh)
        zeros = jnp.zeros_like(q[:, :, :, 0])
        q = jnp.stack([jnp.concatenate([q[:, :, :, 0], zeros], -1),
                       jnp.concatenate([zeros, q[:, :, :, 1]], -1)], axis=3)
        return q.reshape(B, S, Hq, 2 * Dh).transpose(0, 2, 1, 3)

    def combine(self, attn, precise: bool = False):
        """attn (B, Hq, S, 2 Dh), the two softmaxes of each pair applied
        to [v1; v2] -> the layer's output (B, S, d)."""
        c = self.cfg
        B, Hq, S, D2 = attn.shape
        lam_init = c.lambda_init(self.layer)
        lam = (jnp.exp(jnp.sum(self.lambda_q1 * self.lambda_k1))
               - jnp.exp(jnp.sum(self.lambda_q2 * self.lambda_k2))
               + lam_init)
        a = attn.astype(jnp.float32).reshape(B, Hq // 2, 2, S, D2)
        o = a[:, :, 0] - lam * a[:, :, 1]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + c.norm_eps) * self.subln
        o = o * (1.0 - lam_init)
        return self.o_proj(o.transpose(0, 2, 1, 3).reshape(B, S, c.d_model),
                           precise)


# ---------------------------------------------------------------------------
# Mamba-1 and the Gated Memory Unit
# ---------------------------------------------------------------------------


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """softplus^-1 of a step size drawn log-uniformly from [1e-3, 1e-1]
    (the family's published initialiser)."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jnp.broadcast_to(
        jnp.arange(1, shape[1] + 1, dtype=jnp.float32), shape)).astype(dtype)


class Mamba(nn.Module):
    """Mamba-1 (Gu & Dao 2023) with a low-rank step size and a conv bias.
    State of one sequence: the last d_conv - 1 inputs of the conv,
    (d_conv - 1, E) in the served type, and the scan state (N, E) float32."""
    cfg: SambaYConfig

    def setup(self):
        c = self.cfg
        E, N, R = c.d_inner, c.d_state, c.dt_rank
        self.in_proj = Linear(2 * E, c.dtype)
        self.conv_w = self.param("conv_w", nn.initializers.normal(0.2),
                                 (c.d_conv, E), c.dtype)
        self.conv_b = self.param("conv_b", nn.initializers.zeros, (E,),
                                 c.dtype)
        self.x_proj = Linear(R + 2 * N, c.dtype)
        self.dt_proj = Linear(E, c.dtype, use_bias=True,
                              bias_init=_dt_bias_init)
        self.a_log = self.param("a_log", _a_log_init, (E, N), jnp.float32)
        self.d_skip = self.param("d_skip", nn.initializers.ones, (E,),
                                 jnp.float32)
        self.out_proj = Linear(c.d_model, c.dtype)

    def _selective(self, u, precise: bool = False):
        c = self.cfg
        r, b_sel, c_sel = jnp.split(
            self.x_proj(u, precise), [c.dt_rank, c.dt_rank + c.d_state],
            axis=-1)
        delta = jax.nn.softplus(
            self.dt_proj(r, precise).astype(jnp.float32))
        return delta, b_sel, c_sel

    def __call__(self, h, last_idx=None, precise: bool = False, state=None,
                 start=0):
        """h (B, S, d) -> (out, y, state): y (B, S, E) is the scan output
        before the gate (the memory, where this is the memory layer);
        state = (conv window, scan state) after each row's `last_idx`
        (after its last position where None).  `state`: the same before
        these positions, which begin at `start` (may be traced; nothing
        before them where None).  A row whose `last_idx` lies before
        `start` keeps its state."""
        c = self.cfg
        B, S, _ = h.shape
        K = c.d_conv
        keep = (lambda a: a) if precise else (lambda a: a.astype(c.dtype))
        u_in, z = jnp.split(self.in_proj(h, precise), 2, axis=-1)
        conv, s_prev = state if state is not None else (
            jnp.zeros((B, K - 1, c.d_inner), u_in.dtype), None)
        u_pad = jnp.concatenate([conv.astype(u_in.dtype), u_in], axis=1)
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        u = sum(f32(u_pad[:, i: i + S]) * f32(self.conv_w[i])
                for i in range(K))
        u = keep(jax.nn.silu(u + f32(self.conv_b)))
        delta, b_sel, c_sel = self._selective(u, precise)
        # each row's last position among these S: -1 where it lies before
        # them, S - 1 where after
        last = jnp.full((B,), S - 1, jnp.int32) if last_idx is None \
            else jnp.clip(last_idx - start, -1, S - 1)
        # A position past a row's last token leaves the state alone.
        delta = jnp.where(jnp.arange(S)[None, :, None]
                          <= last[:, None, None], delta, 0.0)
        with jax.named_scope("selective_scan"):
            y, s_last = chunked_selective_scan(
                delta, u, b_sel, c_sel, -jnp.exp(self.a_log), s_prev)
        y = keep(y + self.d_skip * u.astype(jnp.float32))
        # padded position p holds input p - (K - 1): the K - 1 inputs that
        # end at `last` are padded positions last + 1 .. + K - 1 (at -1:
        # the window handed in)
        window = jnp.take_along_axis(
            u_pad, (last[:, None] + 1 + jnp.arange(K - 1))[:, :, None],
            axis=1).astype(c.dtype)
        return (self.out_proj(y * jax.nn.silu(f32(z)), precise), y,
                (window, s_last))

    def step(self, h, state, live=None, precise: bool = True):
        """One token: h (B, d), state as above -> (out, y, new state).  A
        row where `live` is False keeps its state."""
        conv, s_prev = state
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        u_in, z = jnp.split(self.in_proj(h, precise), 2, axis=-1)
        # (the stored inputs are the served type's; this step's is not
        # rounded before it is used)
        window = jnp.concatenate([f32(conv), f32(u_in)[:, None]], axis=1)
        u = jax.nn.silu(jnp.sum(window * f32(self.conv_w), axis=1)
                        + f32(self.conv_b))
        delta, b_sel, c_sel = self._selective(u, precise)
        with jax.named_scope("state_step"):
            a_t = -jnp.exp(self.a_log).T                          # (N, E)
            s = jnp.exp(delta[:, None, :] * a_t) * s_prev \
                + (delta * f32(u))[:, None, :] * f32(b_sel)[:, :, None]
            y = jnp.einsum("bne,bn->be", s, f32(c_sel)) \
                + self.d_skip * f32(u)
        new = (window[:, 1:].astype(conv.dtype), s)
        if live is not None:
            new = (jnp.where(live[:, None, None], new[0], conv),
                   jnp.where(live[:, None, None], s, s_prev))
        return self.out_proj(y * jax.nn.silu(f32(z)), precise), y, new


class GMU(nn.Module):
    """Gated Memory Unit: the memory of the last Mamba layer at the same
    position, gated by this layer's input.  No state of its own."""
    cfg: SambaYConfig

    def setup(self):
        c = self.cfg
        self.in_proj = Linear(c.d_inner, c.dtype)
        self.out_proj = Linear(c.d_model, c.dtype)

    def __call__(self, h, memory, precise: bool = False):
        gate = jax.nn.silu(self.in_proj(h, precise))
        return self.out_proj(memory.astype(gate.dtype) * gate, precise)


# ---------------------------------------------------------------------------
# The stack
# ---------------------------------------------------------------------------


class Layer(nn.Module):
    cfg: SambaYConfig
    index: int

    def setup(self):
        c = self.cfg
        kind = c.kind(self.index)
        self.input_norm = LayerNorm(c.norm_eps)
        self.post_norm = LayerNorm(c.norm_eps)
        self.mlp = MLP(c)
        if kind == "mamba":
            self.mamba = Mamba(c)
        elif kind == "gmu":
            self.gmu = GMU(c)
        else:
            self.attn = DiffAttention(c, self.index, cross=kind == "cross")

    def mix(self, x, mixer, precise: bool = False):
        """x += mixer(LN(x)); x += MLP(LN(x)), the stream in float32;
        whatever else the mixer returns (state, memory) is handed back
        beside x."""
        out, *rest = mixer(self.input_norm(x))
        x = x + out
        return (x + self.mlp(self.post_norm(x), precise), *rest)


def _ring_slots(last_idx, window: int):
    """For each ring slot r the position of the newest token t <= last_idx
    with t % window == r (negative: the row has no such token yet)."""
    r = jnp.arange(window)[None, :]
    return last_idx[:, None] - (last_idx[:, None] - r) % window


def _up_to(last_idx, S: int):
    """The mask of one query a row over S keys: those up to its row's
    `last_idx`."""
    return (jnp.arange(S)[None, :] <= last_idx[:, None])[
        :, None, None, None, :]


class SambaYModel(nn.Module):
    cfg: SambaYConfig

    def setup(self):
        c = self.cfg
        self.embed = nn.Embed(c.vocab_size, c.d_model, dtype=c.dtype,
                              param_dtype=c.dtype)
        self.layers = [Layer(c, i) for i in range(c.n_layers)]
        self.norm = LayerNorm(c.norm_eps)

    # ---- layers 0 .. L/2 over positions after a state ----------------------

    def fresh_state(self, B: int):
        """What `_carried` and `prompt_block` carry, before a row's first
        position: {"mamba": [(conv window, scan state)], "rings": [(k, v)]
        (B, Hkv/2, window, 2 Dh) in slot order, "x" (B, d), "memory"
        (B, E): the stream after layer L/2 and the memory at each row's
        last token}, all zeros."""
        c = self.cfg
        ring = (B, c.kv_pairs, c.window, 2 * c.head_dim)
        return {
            "mamba": [(jnp.zeros((B, c.d_conv - 1, c.d_inner), c.dtype),
                       jnp.zeros((B, c.d_state, c.d_inner), jnp.float32))
                      for _ in c.layers_of("mamba")],
            "rings": [(jnp.zeros(ring, c.dtype), jnp.zeros(ring, c.dtype))
                      for _ in c.layers_of("window")],
            "x": jnp.zeros((B, c.d_model), jnp.float32),
            "memory": jnp.zeros((B, c.d_inner), c.dtype)}

    def _carried(self, x, start, last_idx, state, precise: bool = False):
        """Layers 0 .. L/2, the layers that carry state along a row, over
        x (B, S, d) at positions `start` .. (a multiple of `window`, may be
        traced) after `state` -> x, the memory (B, S, E), and the state
        after them.  A ring kept in slot order IS the window before these
        positions where a row goes on through them, so a window layer
        attends over it and its own keys.  A row's state stops at its
        `last_idx`: one that ended before `start` keeps it, and its x and
        memory at that token are kept as they were found."""
        c = self.cfg
        B, S, _ = x.shape
        # ring slot r after these positions: the newest token t up to the
        # row's last one here with t % window == r, or what the ring held
        slot_pos = _ring_slots(jnp.minimum(last_idx, start + S - 1),
                               c.window) - start                # (B, W)
        at = jnp.maximum(slot_pos, 0)[:, None, :, None]
        here = (slot_pos >= 0)[:, None, :, None]
        mamba, rings = [], []
        memory = None
        for i in range(c.n_self - 1):
            layer = self.layers[i]
            if c.kind(i) == "mamba":
                before = state["mamba"][len(mamba)]
                x, memory, after = layer.mix(x, lambda h: layer.mamba(
                    h, last_idx, precise, before, start), precise)
                mamba.append(after)
                continue
            attn, before = layer.attn, state["rings"][len(rings)]

            def mixer(h):
                q, k, v = attn.project(h, precise)
                o = window_attention(q, k, v, c.window, attn.sm_scale,
                                     precise, before, start)
                return attn.combine(o, precise), k, v

            x, k, v = layer.mix(x, mixer, precise)
            rings.append(tuple(
                jnp.where(here, jnp.take_along_axis(a, at, axis=2), old)
                for a, old in zip((k, v), before)))
        last = jnp.clip(last_idx - start, 0, S - 1)[:, None, None]
        ends_here = ((last_idx >= start) & (last_idx < start + S))[:, None]
        return x, memory, {
            "mamba": mamba, "rings": rings,
            "x": jnp.where(ends_here, jnp.take_along_axis(
                x, last, axis=1)[:, 0], state["x"]),
            "memory": jnp.where(ends_here, jnp.take_along_axis(
                memory, last, axis=1)[:, 0].astype(c.dtype),
                state["memory"])}

    def _head(self, x, precise: bool):
        with jax.named_scope("head"):
            return matmul(self.norm(x), self.embed.embedding.T,
                          precise).astype(jnp.float32)

    def _cross_decoder(self, x, memory, k, v, mask, precise: bool):
        """Layers L/2+2 .. over x (B, S, d) with the memory at the same
        positions and the cache's keys under `mask`; then the logits."""
        c = self.cfg
        for i in range(c.n_self, c.n_layers):
            layer = self.layers[i]
            if c.kind(i) == "gmu":
                x, = layer.mix(
                    x, lambda h: (layer.gmu(h, memory, precise),), precise)
                continue
            attn = layer.attn

            def mixer(h):
                q, _, _ = attn.project(h, precise)
                return (attn.combine(masked_attention(
                    q, k, v, mask, attn.sm_scale, precise), precise),)

            x, = layer.mix(x, mixer, precise)
        return self._head(x, precise)

    def __call__(self, tokens, precise: bool = False):
        """Whole forward: (B, S) -> float32 logits (B, S, V), every layer
        at every position."""
        c = self.cfg
        B, S = tokens.shape
        x, memory, _ = self._carried(
            self.embed(tokens).astype(jnp.float32), 0,
            jnp.full((B,), S - 1, jnp.int32), self.fresh_state(B), precise)
        layer = self.layers[c.n_self - 1]
        attn = layer.attn

        def mixer(h):
            q, k, v = attn.project(h, precise)
            return attn.combine(causal_attention(
                q, k, v, attn.sm_scale, c.attention, precise), precise), k, v

        x, k, v = layer.mix(x, mixer, precise)
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        return self._cross_decoder(x, memory, k, v, mask, precise)

    # ---- a prompt, a block of positions a program -------------------------

    def prompt_block(self, tokens, start, last_idx, state):
        """One block of right-padded prompts: tokens (B, block) at positions
        `start` .. (a traced scalar, a multiple of `window`: every block of
        a width is ONE program), each row's last token at `last_idx`,
        `state` as the block before left it (`fresh_state` before the
        first) -> the state after the block, and the full layer's K and V
        of the block, (B, Hkv/2, block, 2 Dh): its cache.  A row that ended
        in an earlier block computes what nothing reads."""
        layer = self.layers[self.cfg.n_self - 1]
        x, _, state = self._carried(
            self.embed(tokens).astype(jnp.float32), start, last_idx, state)
        return state, layer.attn.keys_values(layer.input_norm(x))

    def prompt_tail(self, state, k, v, last_idx):
        """After a prompt's last block: `state` as `prompt_block` left it,
        K and V of the full layer over the rows (B, Hkv/2, S, 2 Dh) ->
        float32 logits (B, V) at each row's last token.  Of the full layer
        nothing but K and V is read at another position, and a cross layer
        feeds nothing but its own position's logits: so the full layer
        attends, projects out and feeds forward for ONE query a row under
        `<= last_idx`, and the cross-decoder and the head run at that
        token; one token a row, so every product with two terms.  No
        `flash_attention` call here."""
        layer = self.layers[self.cfg.n_self - 1]
        attn = layer.attn
        seen = _up_to(last_idx, k.shape[2])
        x, = layer.mix(
            state["x"][:, None], lambda h: (attn.combine(masked_attention(
                attn.queries(h, True), k, v, seen, attn.sm_scale, True),
                True),), True)
        return self._cross_decoder(x, state["memory"][:, None], k, v, seen,
                                   True)[:, 0]

    # ---- one token a sequence ---------------------------------------------

    def decode(self, token, state, table, length, live=None):
        """token (B,), `length` (B,) tokens already cached -> float32
        logits (B, V) and the state with this token in it.  state:
        {"mamba", "rings"} as `prompt_block` leaves them (batch-first) and
        "pool": (k_pool, v_pool) (P, Hkv/2, page, 2 Dh) under `table`
        (B, NP).  A row where `live` is False keeps rings and Mamba state
        (its pool write lands where its next live step writes again)."""
        from ray_tpu.ops.paged_attention import paged_decode_attention_batch

        c = self.cfg
        B = token.shape[0]
        rows = jnp.arange(B)
        x = self.embed(token).astype(jnp.float32)              # (B, d)
        mamba, rings = [], []
        k_pool, v_pool = state["pool"]
        memory = None

        def lift(f):        # a mixer over (B, d) as one over (B, 1, d)
            return lambda h: tuple(
                a[:, None] if j == 0 else a
                for j, a in enumerate(f(h[:, 0])))

        def paged(q, sm_scale, **new):
            # (float32 queries: the kernel computes in float32 whatever
            # they are, and hands back their type)
            return paged_decode_attention_batch(
                q[:, :, 0].astype(jnp.float32), k_pool, v_pool, table,
                length + 1, sm_scale=sm_scale, **new)

        x = x[:, None]
        for i in range(c.n_layers):
            layer, kind = self.layers[i], c.kind(i)
            if kind == "mamba":
                prev = state["mamba"][len(mamba)]
                x, memory, new = layer.mix(x, lift(
                    lambda h: layer.mamba.step(h, prev, live)), True)
                mamba.append(new)
            elif kind == "gmu":
                x, = layer.mix(x, lift(
                    lambda h: (layer.gmu(h, memory, True),)), True)
            elif kind == "window":
                attn = layer.attn
                rk, rv = state["rings"][len(rings)]
                slot = length % c.window

                def mixer(h):
                    q, k, v = attn.project(h, True)    # (B, H, 1, 2 Dh)
                    k, v = k[:, :, 0], v[:, :, 0]
                    if live is not None:
                        keep = live[:, None, None]
                        k = jnp.where(keep, k, rk[rows, :, slot])
                        v = jnp.where(keep, v, rv[rows, :, slot])
                    # (A write of one token a row makes the compiler carry
                    # the buffer through the decode loop token-major
                    # (layout {3,1,2,0}) and copy it whole to the layout
                    # of whoever reads it, every step.  The pool's write
                    # moved into the kernel that reads it (PR 29); a
                    # ring has no kernel, and held token-major it is
                    # copied all the same, by the dot that reads it:
                    # PERF.md section 6, PR 29.)
                    with jax.named_scope("ring"):
                        nk = rk.at[rows, :, slot].set(k)
                        nv = rv.at[rows, :, slot].set(v)
                        seen = (jnp.arange(c.window)[None, :]
                                <= length[:, None])[:, None, None, None, :]
                        o = masked_attention(q, nk, nv, seen,
                                             attn.sm_scale, True)
                    return attn.combine(o, True), nk, nv

                x, nk, nv = layer.mix(x, mixer, True)
                rings.append((nk, nv))
            elif kind == "full":
                attn = layer.attn

                def mixer(h):
                    # the kernel puts this token into the pool, in place,
                    # before it or any later layer reads it
                    q, k, v = attn.project(h, True)
                    o, kp, vp = paged(q, attn.sm_scale, k_new=k[:, :, 0],
                                      v_new=v[:, :, 0])
                    return attn.combine(o[:, :, None], True), kp, vp

                x, k_pool, v_pool = layer.mix(x, mixer, True)
            else:
                attn = layer.attn
                x, = layer.mix(x, lambda h: (attn.combine(paged(
                    attn.project(h, True)[0], attn.sm_scale)[:, :, None],
                    True),), True)
        logits = self._head(x[:, 0], True)
        return logits, {"mamba": mamba, "rings": rings,
                        "pool": (k_pool, v_pool)}


# ---------------------------------------------------------------------------
# Initialiser and counts
# ---------------------------------------------------------------------------

# Projections that write into the residual stream.
_RESIDUAL_OUT = ("o_proj", "out_proj", "down_proj")


def init_params(cfg: SambaYConfig, key):
    """A published-style initialiser: every matrix normal(0, 0.02), those
    that write into the residual stream scaled by 1 / sqrt(2 L) (GPT-2's
    rule, so that the stream's variance does not grow with depth); the
    embedding normal(0, 0.02); norms 1 / 0; Mamba's own parameters and
    the lambda vectors as their modules draw them."""
    model = SambaYModel(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    drawn = model.init(key, tokens)
    flat = jax.tree_util.tree_flatten_with_path(drawn)[0]
    keys = jax.random.split(jax.random.fold_in(key, 1), len(flat))
    out = []
    for (path, leaf), k in zip(flat, keys):
        names = [p.key for p in path]
        if names[-1] in ("kernel", "embedding"):
            std = 0.02
            if names[-2] in _RESIDUAL_OUT:
                std /= math.sqrt(2 * cfg.n_layers)
            leaf = (jax.random.normal(k, leaf.shape, jnp.float32)
                    * std).astype(leaf.dtype)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(drawn),
                                        out)


def count_params(cfg: SambaYConfig) -> dict:
    """Parameters by kind of layer (one layer of each) and in all."""
    d, ff, E, N, R = cfg.d_model, cfg.d_ff, cfg.d_inner, cfg.d_state, \
        cfg.dt_rank
    Dh = cfg.head_dim
    mlp = 3 * d * ff + 4 * d                  # + the layer's two norms
    lam = 4 * Dh + 2 * Dh
    one = {
        "mamba": mlp + 2 * d * E + cfg.d_conv * E + E + E * (R + 2 * N)
        + R * E + E + E * N + E + E * d,
        "window": mlp + d * (cfg.n_heads + 2 * cfg.n_kv_heads) * Dh
        + cfg.n_heads * Dh * d + lam,
        "gmu": mlp + 2 * d * E,
        "cross": mlp + 2 * d * cfg.n_heads * Dh + lam,
    }
    one["full"] = one["window"]
    total = sum(one[cfg.kind(i)] for i in range(cfg.n_layers)) \
        + cfg.vocab_size * d + 2 * d
    return dict(one, embedding=cfg.vocab_size * d, total=total)
