"""Plain reference for both configurations: a pre-norm decoder with rotary
grouped-query attention and a gated SiLU feed-forward, as the Mistral-7B
and InternLM2 descriptions give it.  Straightforward `jax.numpy` in float32
at "highest" matmul precision: no cache, no kernels, no batching tricks.

Departures from the published code, both arithmetic-neutral: InternLM2's
fused `wqkv` is read as separate q/k/v matrices (the same products), and
rotary pairs element i with i + head_dim/2 (the "rotate half" layout the
public checkpoints of both models use).

Weights come from the system under test (its own parameter tree, upcast a
layer at a time), so the comparison is of arithmetic, not of initialisers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISION = "highest"


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    # x: (S, H, D); pairs (i, i + D/2)
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv_heads",
                                             "theta", "eps"))
def layer(x, p, *, n_heads, n_kv_heads, theta, eps):
    """One decoder layer over one sequence x: (S, d), causal."""
    with jax.default_matmul_precision(PRECISION):
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        S, d = x.shape
        dh = d // n_heads
        pos = jnp.arange(S)
        h = _rms_norm(x, f32(p["input_norm"]["scale"]), eps)
        q = (h @ f32(p["attn"]["q_proj"]["kernel"])).reshape(S, n_heads, dh)
        k = (h @ f32(p["attn"]["k_proj"]["kernel"])).reshape(S, n_kv_heads, dh)
        v = (h @ f32(p["attn"]["v_proj"]["kernel"])).reshape(S, n_kv_heads, dh)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        rep = n_heads // n_kv_heads
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(dh))
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        x = x + a.reshape(S, d) @ f32(p["attn"]["o_proj"]["kernel"])
        h = _rms_norm(x, f32(p["post_attn_norm"]["scale"]), eps)
        gate = h @ f32(p["mlp"]["gate_proj"]["kernel"])
        up = h @ f32(p["mlp"]["up_proj"]["kernel"])
        return x + (jax.nn.silu(gate) * up) @ f32(
            p["mlp"]["down_proj"]["kernel"])


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, norm_scale, head_kernel, *, eps):
    with jax.default_matmul_precision(PRECISION):
        x = _rms_norm(x, norm_scale.astype(jnp.float32), eps)
        return x @ head_kernel.astype(jnp.float32)


def hidden_states(params: dict, sizes: dict, tokens) -> jax.Array:
    """Final hidden states (S, d) of one sequence of token ids."""
    p = params["params"]
    x = p["embed"]["embedding"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(sizes["num_hidden_layers"]):
        x = layer(x, p[f"layers_{i}"],
                  n_heads=sizes["num_attention_heads"],
                  n_kv_heads=sizes["num_key_value_heads"],
                  theta=float(sizes["rope_theta"]),
                  eps=float(sizes["rms_norm_eps"]))
    return x


def logits(params: dict, sizes: dict, tokens, rows=None) -> jax.Array:
    """Float32 logits of one sequence, at `rows` (all positions if None)."""
    p = params["params"]
    x = hidden_states(params, sizes, tokens)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    kernel = (p["embed"]["embedding"].T if sizes["tie_word_embeddings"]
              else p["lm_head"]["kernel"])
    return head(x, p["norm"]["scale"], kernel,
                eps=float(sizes["rms_norm_eps"]))


def mean_token_loss(params: dict, sizes: dict, inputs, targets) -> float:
    """Mean next-token cross-entropy over rows of (inputs, targets)."""
    total, count = 0.0, 0
    for inp, tgt in zip(inputs, targets):
        lg = logits(params, sizes, inp)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, jnp.asarray(tgt)[:, None], -1)[:, 0]
        total += float(jnp.sum(lse - picked))
        count += len(tgt)
    return total / count
