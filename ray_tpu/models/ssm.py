"""Selective state-space model (Mamba-family), TPU-first.

Rounds out the model zoo with the SSM architecture class. The TPU-native
angle: the recurrence h_t = a_t * h_{t-1} + b_t is evaluated with
`jax.lax.associative_scan` — O(log S) depth parallel prefix instead of a
sequential loop, which is the difference between MXU/VPU-friendly and
latency-bound on TPU (`_selective_scan`; it materialises (B, S, E, N)
float32 decay, drive and state: 5.4 GB each for one 16k prompt at E
5120). The models run `chunked_selective_scan` instead: a `lax.scan`
over the sequence whose body is a chunk of steps unrolled, so that only
the state (B, N, E) and a chunk's inputs are live, each step is
contracted with C at once, and the compiler fuses a chunk into a few
passes; on the v5e that was 3 to 15 times faster than the prefix, which
is bound by the memory it writes. Decode
is O(1) per token: `init_ssm_state` / `ssm_decode_step` carry the
per-layer SSM state (E,N) and the depthwise conv window (d_conv-1, E) —
the SSM advantage over attention's O(S) KV cache.

Structure follows the Mamba block shape (Gu & Dao 2023, public
architecture): in-proj to a gated pair, short depthwise causal conv,
input-selective (Δ, B, C), diagonal A, gated out-proj. Implementation is
original and jnp-only.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.parallel.sharding import P, ShardingRules


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    vocab_size: int = 32000
    d_model: int = 768
    n_layers: int = 24
    d_state: int = 16          # per-channel SSM state size
    d_conv: int = 4            # depthwise conv width
    expand: int = 2            # inner width = expand * d_model
    dtype: Any = jnp.bfloat16

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model


MAMBA_130M = SSMConfig(d_model=768, n_layers=24)
MAMBA_790M = SSMConfig(d_model=1536, n_layers=48)
TINY_SSM = SSMConfig(vocab_size=256, d_model=64, n_layers=2, d_state=8,
                     expand=2, dtype=jnp.float32)


def _selective_scan(a, b):
    """First-order linear recurrence h_t = a_t * h_{t-1} + b_t over axis 1
    via parallel prefix. a, b: (B, S, E, N)."""

    def combine(left, right):
        a_l, b_l = left
        a_r, b_r = right
        return a_l * a_r, a_r * b_l + b_r

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h


# Positions one iteration of the scan's loop advances: its body is that many
# steps unrolled, fused by the compiler into a few passes over the state. On
# the v5e, E 5120 x N 16: 14.6 ms for 16,384 positions at 32, 21.8 ms at 8,
# 46 ms at 1; a parallel prefix over chunks of 64 took 40 ms for one row and
# 224 ms for eight rows of 2,048 (my chip runs, PR 28).
_SCAN_CHUNK = 32


def chunked_selective_scan(delta, u, b_sel, c_sel, a, h0=None, *,
                           chunk: int = _SCAN_CHUNK):
    """The selective scan of a Mamba-1 layer, a chunk of the sequence at a
    time:  h_t = exp(delta_t * A) * h_{t-1} + (delta_t * u_t) (x) B_t,
    y_t = h_t . C_t.

    delta, u: (B, S, E); b_sel, c_sel: (B, S, N); a: (E, N), negative;
    h0: (B, N, E) float32 or None (zeros). Returns y (B, S, E) float32
    and the state after the last position, (B, N, E) float32. A position
    whose delta is 0 leaves the state as it was (decay 1, drive 0): that
    is how a right-padded row keeps the state of its own last token.

    Only the state (B, N, E) and one chunk's inputs are live at a time:
    each step is contracted with C as soon as it is made, and the state
    crosses from chunk to chunk as the carry of a `lax.scan` whose body is
    `chunk` steps. E is the minor axis (the lanes; N = 16 there would be
    padded eightfold).
    """
    B, S, E = delta.shape
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    a_t = f32(a).T                                  # (N, E)
    h0 = jnp.zeros((B, a.shape[1], E), jnp.float32) if h0 is None else f32(h0)

    def step(h, xs):
        d, x, bs, cs = (f32(v) for v in xs)
        h = jnp.exp(d[:, None, :] * a_t) * h \
            + (d * x)[:, None, :] * bs[:, :, None]
        return h, jnp.einsum("bne,bn->be", h, cs)

    h_last, y = jax.lax.scan(
        step, h0, tuple(x.swapaxes(0, 1) for x in (delta, u, b_sel, c_sel)),
        unroll=max(1, min(chunk, S)))
    return y.swapaxes(0, 1), h_last


class SSMBlock(nn.Module):
    cfg: SSMConfig

    @nn.compact
    def __call__(self, x, state=None, return_state: bool = False):
        """state=None: full-sequence parallel forward -> y, or
        (y, final_state) with return_state=True (the O(log S) prefill —
        sequential per-token priming would be exactly the latency-bound
        pattern the scan exists to avoid).
        state=(conv_window, h): O(1) single-token step (S must be 1)
        -> (y, new_state). conv_window: (B, d_conv-1, E) last pre-conv
        activations; h: (B, E, N) f32 SSM state."""
        c = self.cfg
        B, S, _ = x.shape
        E, N = c.d_inner, c.d_state
        dense = lambda n, name, bias=False: nn.Dense(
            n, use_bias=bias, dtype=c.dtype, param_dtype=c.dtype, name=name)

        xz = dense(2 * E, "in_proj")(x)
        u_in, z = jnp.split(xz, 2, axis=-1)       # (B,S,E) each

        # Short depthwise causal conv (local mixing before the SSM).
        conv_w = self.param("conv_w", nn.initializers.normal(0.02),
                            (c.d_conv, E), c.dtype)
        if state is None:
            u_pad = jnp.pad(u_in, ((0, 0), (c.d_conv - 1, 0), (0, 0)))
            # Next decode step needs the last d_conv-1 pre-conv activations.
            window = u_pad[:, S:]
            u = sum(u_pad[:, i: i + S] * conv_w[i][None, None]
                    for i in range(c.d_conv))
        else:
            if S != 1:
                raise ValueError(
                    f"stateful SSM step requires S==1, got S={S}; prime a "
                    "prompt with the parallel forward (return_state=True)")
            conv_state, h_prev = state
            window = jnp.concatenate([conv_state, u_in], axis=1)  # (B,d_conv,E)
            u = sum(window[:, i: i + 1] * conv_w[i][None, None]
                    for i in range(c.d_conv))                      # (B,1,E)
        u = jax.nn.silu(u)

        # Input-selective SSM parameters.
        delta = jax.nn.softplus(dense(E, "dt_proj", bias=True)(u))  # (B,S,E)
        Bsel = dense(N, "b_proj")(u)                                # (B,S,N)
        Csel = dense(N, "c_proj")(u)                                # (B,S,N)
        # Diagonal A < 0 for stability; log-parameterized.
        a_log = self.param("a_log", nn.initializers.normal(0.5), (E, N),
                           jnp.float32)
        A = -jnp.exp(a_log)                                          # (E,N)

        if state is None:
            # (B,S,E,N) is never whole in memory: a chunk at a time.
            y, h_last = chunked_selective_scan(delta, u, Bsel, Csel, A)
            h_last = h_last.swapaxes(1, 2)                           # (B,E,N)
        else:
            d32 = delta.astype(jnp.float32)
            decay = jnp.exp(d32[..., None] * A[None, None])          # (B,1,E,N)
            drive = (d32 * u.astype(jnp.float32))[..., None] * \
                Bsel.astype(jnp.float32)[:, :, None, :]
            h_new = decay[:, 0] * h_prev + drive[:, 0]               # (B,E,N)
            y = jnp.einsum("bsen,bsn->bse", h_new[:, None],
                           Csel.astype(jnp.float32))
        D = self.param("d_skip", nn.initializers.ones, (E,), jnp.float32)
        y = (y + D[None, None] * u.astype(jnp.float32)).astype(c.dtype)

        y = y * jax.nn.silu(z)
        out = dense(c.d_model, "out_proj")(y)
        if state is not None:
            return out, (window[:, 1:], h_new)
        if return_state:
            return out, (window, h_last)
        return out


class SSMModel(nn.Module):
    """Decoder-only SSM language model (Mamba-style residual stack)."""

    cfg: SSMConfig

    @nn.compact
    def __call__(self, tokens, states=None, return_states: bool = False):
        """states=None: (B,S) -> (B,S,V) logits; with return_states=True
        -> (logits, states) — the parallel PREFILL priming decode.
        states=[per-layer (conv_window, h)]: (B,1) single-token decode ->
        (logits (B,1,V), new_states). Build fresh states with
        init_ssm_state or prime them with the prefill form."""
        c = self.cfg
        embed = nn.Embed(c.vocab_size, c.d_model, dtype=c.dtype,
                         param_dtype=c.dtype, name="tok_embed")
        x = embed(tokens)
        new_states = []
        for i in range(c.n_layers):
            h = nn.RMSNorm(epsilon=1e-5, dtype=jnp.float32,
                           name=f"norm_{i}")(x).astype(c.dtype)
            block = SSMBlock(c, name=f"block_{i}")
            if states is not None:
                y, st = block(h, states[i])
            elif return_states:
                y, st = block(h, return_state=True)
            else:
                y, st = block(h), None
            x = x + y
            if st is not None:
                new_states.append(st)
        x = nn.RMSNorm(epsilon=1e-5, dtype=jnp.float32, name="norm_f")(x)
        logits = embed.attend(x.astype(c.dtype))
        if states is None and not return_states:
            return logits
        return logits, new_states


# Mesh sharding rules (same idiom as TRANSFORMER_RULES/MOE_RULES): TP
# shards the inner channel dim E, FSDP the other matrix dim; the tiny
# d_state axis stays replicated.
SSM_RULES = ShardingRules([
    (r"tok_embed/embedding", P("fsdp", "tp")),
    (r"in_proj/kernel", P("fsdp", "tp")),
    (r"out_proj/kernel", P("tp", "fsdp")),
    (r"dt_proj/kernel", P("fsdp", "tp")),
    (r"(b_proj|c_proj)/kernel", P("fsdp", None)),
    (r"conv_w", P(None, "tp")),
    (r"a_log", P("tp", None)),
    (r"d_skip", P("tp")),
    (r"(norm|scale|bias)", P()),
], default=P())


def init_ssm_state(cfg: SSMConfig, batch: int):
    """Fresh per-layer decode state: conv window + SSM state, all zeros
    (the attention-KV-cache analog, but O(1) in sequence length)."""
    E, N = cfg.d_inner, cfg.d_state
    return [(jnp.zeros((batch, cfg.d_conv - 1, E), cfg.dtype),
             jnp.zeros((batch, E, N), jnp.float32))
            for _ in range(cfg.n_layers)]


def ssm_prefill(model: SSMModel, params, tokens):
    """Prime decode state from a prompt in ONE parallel forward (O(log S)
    scan depth): tokens (B,S) -> (last_logits (B,V), states)."""
    logits, states = model.apply(params, tokens, return_states=True)
    return logits[:, -1], states


def ssm_decode_step(model: SSMModel, params, token, states):
    """One O(1) decode step: token (B,) -> (logits (B,V), new_states).
    jit this; the state pytree has static shapes independent of position."""
    logits, new_states = model.apply(params, token[:, None], states)
    return logits[:, 0], new_states
