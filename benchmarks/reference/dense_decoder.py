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

`rounded` (0 by default: nothing) makes the same plain pass with the
roundings a bfloat16 deployment makes, one more at each level (ROUNDINGS):
the check recomputes the reference so to find the positions where the
reference's OWN best token does not survive them (benchmarks/README.md,
"How `correct` is decided").  Every product still accumulates in float32
at "highest": only what a server would store or hand on is rounded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISION = "highest"
# What each level of `rounded` adds, and why a bf16 server rounds there.
ROUNDINGS = (
    "none: float32 throughout",
    "K (after rotary) and V: a bf16 server stores them in its cache",
    "and the residual stream after each add: it is held in bf16 between "
    "layers",
    "and every norm, projection, attention and feed-forward output and the "
    "logits: the whole served type",
)


def _quantised(a, bits: int):
    """`a` (S, H, D) through `bits`-bit integers and back, one scale a
    token and head: the CONTROL of the served check (K and V held in a
    lower precision than any configuration here states).  0: untouched."""
    if not bits:
        return a
    top = 2.0 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(a), -1, keepdims=True) / top
    return jnp.round(a / jnp.maximum(scale, 1e-30)) * scale


def _round(a, rounded: int, level: int):
    """`a` through bfloat16 and back where `rounded` reaches `level`."""
    if rounded >= level:
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    return a


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
                                             "theta", "eps", "rounded",
                                             "kv_bits"))
def layer(x, p, *, n_heads, n_kv_heads, theta, eps, rounded=0, kv_bits=0):
    """One decoder layer over one sequence x: (S, d), causal."""
    with jax.default_matmul_precision(PRECISION):
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        kv = lambda a: _round(a, rounded, 1)  # noqa: E731
        res = lambda a: _round(a, rounded, 2)  # noqa: E731
        act = lambda a: _round(a, rounded, 3)  # noqa: E731
        S, d = x.shape
        dh = d // n_heads
        pos = jnp.arange(S)
        h = act(_rms_norm(x, f32(p["input_norm"]["scale"]), eps))
        q = act(h @ f32(p["attn"]["q_proj"]["kernel"])).reshape(
            S, n_heads, dh)
        k = act(h @ f32(p["attn"]["k_proj"]["kernel"])).reshape(
            S, n_kv_heads, dh)
        v = kv(h @ f32(p["attn"]["v_proj"]["kernel"])).reshape(
            S, n_kv_heads, dh)
        q, k = act(_rope(q, pos, theta)), kv(_rope(k, pos, theta))
        k, v = _quantised(k, kv_bits), _quantised(v, kv_bits)
        rep = n_heads // n_kv_heads
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(dh))
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        a = act(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v))
        x = res(x + act(a.reshape(S, d) @ f32(p["attn"]["o_proj"]["kernel"])))
        h = act(_rms_norm(x, f32(p["post_attn_norm"]["scale"]), eps))
        gate = act(h @ f32(p["mlp"]["gate_proj"]["kernel"]))
        up = act(h @ f32(p["mlp"]["up_proj"]["kernel"]))
        return res(x + act(act(jax.nn.silu(gate) * up) @ f32(
            p["mlp"]["down_proj"]["kernel"])))


@functools.partial(jax.jit, static_argnames=("eps", "rounded"))
def head(x, norm_scale, head_kernel, *, eps, rounded=0):
    with jax.default_matmul_precision(PRECISION):
        x = _round(_rms_norm(x, norm_scale.astype(jnp.float32), eps),
                   rounded, 3)
        return _round(x @ head_kernel.astype(jnp.float32), rounded, 3)


def hidden_states(params: dict, sizes: dict, tokens,
                  rounded: int = 0, kv_bits: int = 0) -> jax.Array:
    """Final hidden states (S, d) of one sequence of token ids."""
    p = params["params"]
    x = p["embed"]["embedding"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(sizes["num_hidden_layers"]):
        x = layer(x, p[f"layers_{i}"],
                  n_heads=sizes["num_attention_heads"],
                  n_kv_heads=sizes["num_key_value_heads"],
                  theta=float(sizes["rope_theta"]),
                  eps=float(sizes["rms_norm_eps"]), rounded=rounded,
                  kv_bits=kv_bits)
    return x


def logits(params: dict, sizes: dict, tokens, rows=None,
           rounded: int = 0, kv_bits: int = 0) -> jax.Array:
    """Float32 logits of one sequence, at `rows` (all positions if None);
    `rounded` as the module's head says, `kv_bits` as `_quantised` does."""
    p = params["params"]
    x = hidden_states(params, sizes, tokens, rounded, kv_bits)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    kernel = (p["embed"]["embedding"].T if sizes["tie_word_embeddings"]
              else p["lm_head"]["kernel"])
    return head(x, p["norm"]["scale"], kernel,
                eps=float(sizes["rms_norm_eps"]), rounded=rounded)


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
