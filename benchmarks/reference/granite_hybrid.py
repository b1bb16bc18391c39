"""Plain reference of the `granite_hybrid` family (Granite-4.0-H-Micro):
the published equations in `jax.numpy`, float32 at "highest" matmul
precision, one sequence at a time, every layer at every position: no
cache, no pages, no kernels, no chunked matrix form, and the Mamba-2
recurrence token by token (a `lax.scan` over positions), so that it is
independent of the program's chunked form.

    h = embedding_multiplier * E[token]
    each layer i, by layer_types[i]:
        h += residual_multiplier * Mixer_i(RMSNorm(h))
        h += residual_multiplier * W_out (silu(a) * b),
             (a, b) = split(W_in RMSNorm(h))
    logits = RMSNorm(h) E^T / logits_scaling

  attention   q 32 heads, k and v 8 heads of 64, query head h reads KV
              head h // 4; causal; scores scaled by attention_multiplier;
              no position term of any kind ("nope")
  mamba       [z | xBC | dt] = W_in h; xBC = silu(conv4(xBC) + b), causal
              and depthwise; x, B, C = split(xBC); dt = softplus(dt +
              dt_bias) a head; A = -exp(A_log) a head; for head i
              S_t[i] = exp(dt_t[i] A[i]) S_{t-1}[i] + dt_t[i] x_t[i] (x) B_t
              y_t[i] = S_t[i] C_t + D[i] x_t[i]
              out = W_out RMSNorm_w(y * silu(z)), the norm over all of y

Departures from the published code, all arithmetic-neutral: q, k and v are
read from one matrix (the system keeps them so; the same products);
attention is computed a block of query rows at a time, the feed-forward a
block of rows at a time and the head a slice of the vocabulary at a time,
only so that a 1,600-token sample fits beside a serving engine (the rows
do not interact).

Weights come from the system under test (its own tree, upcast a layer at a
time).  `rounded` makes the same pass with the roundings a bfloat16 server
makes, one more at each level (ROUNDINGS); the state S stays float32 in
all of them, because the configuration says so.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISION = "highest"
ROUNDINGS = (
    "none: float32 throughout",
    "K and V of every attention layer and the inputs of every conv: a bf16 "
    "server stores them in its pages and conv windows (the state S stays "
    "float32: the configuration keeps it so)",
    "and the residual stream after each add: it is held in bf16 between "
    "layers",
    "and every norm, projection, conv, recurrence, attention, gate and "
    "feed-forward output and the logits: the whole served type",
)
QUERY_BLOCK = 128        # rows of attention scores in flight
ROW_BLOCKS = 4           # the feed-forward runs this many blocks of rows
VOCAB_SLICES = 8


def _round(a, rounded: int, level: int):
    if rounded >= level:
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    return a


def _f32(a):
    return a.astype(jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(scale)


def _in_row_blocks(f, x, blocks: int):
    """f over `blocks` blocks of x's rows, one at a time."""
    S = x.shape[0]
    size = -(-S // blocks)
    padded = jnp.pad(x, ((0, blocks * size - S), (0, 0)))
    out = jax.lax.map(f, padded.reshape(blocks, size, -1))
    return out.reshape(blocks * size, -1)[:S]


def _mlp(h, p, act):
    def rows(h):
        a, b = jnp.split(act(h @ _f32(p["in_proj"]["kernel"])), 2, axis=-1)
        return act(act(jax.nn.silu(a) * b) @ _f32(p["out_proj"]["kernel"]))

    return _in_row_blocks(rows, h, ROW_BLOCKS)


def _mamba(h, p, act, stored, *, heads, d_state, eps):
    S = h.shape[0]
    zxd = act(h @ _f32(p["in_proj"]["kernel"]))
    d_inner = p["out_proj"]["kernel"].shape[0]
    z, xbc, dt = jnp.split(zxd, [d_inner, zxd.shape[1] - heads], axis=-1)
    w = _f32(p["conv_w"])                                   # (K, channels)
    K = w.shape[0]
    pad = jnp.pad(stored(xbc), ((K - 1, 0), (0, 0)))
    xbc = sum(pad[i: i + S] * w[i] for i in range(K)) + _f32(p["conv_b"])
    xbc = act(jax.nn.silu(xbc))
    x, b_sel, c_sel = jnp.split(xbc, [d_inner, d_inner + d_state], axis=-1)
    x = x.reshape(S, heads, -1)                             # (S, H, P)
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))           # (S, H)
    a = -jnp.exp(_f32(p["a_log"]))                          # (H,)

    def step(s, xs):                    # s (H, P, N): one token
        dt_t, x_t, b_t, c_t = xs
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return s, jnp.sum(s * c_t[None, None, :], axis=-1)

    _, y = jax.lax.scan(
        step, jnp.zeros((heads, x.shape[-1], d_state), jnp.float32),
        (dt, x, b_sel, c_sel))
    y = act(y + _f32(p["d_skip"])[:, None] * x).reshape(S, d_inner)
    y = act(_rms_norm(act(y * jax.nn.silu(z)), p["norm"]["scale"], eps))
    return act(y @ _f32(p["out_proj"]["kernel"]))


def _attention(h, p, act, stored, *, n_heads, n_kv_heads, scale):
    S, d = h.shape
    Dh = d // n_heads
    qkv = act(h @ _f32(p["qkv_proj"]["kernel"]))
    q, k, v = jnp.split(qkv, [n_heads * Dh, (n_heads + n_kv_heads) * Dh], -1)
    q = q.reshape(S, n_heads, Dh)
    k = k.reshape(S, n_kv_heads, Dh)
    v = v.reshape(S, n_kv_heads, Dh)
    kv_of = jnp.arange(n_heads) // (n_heads // n_kv_heads)
    k, v = stored(k)[:, kv_of], stored(v)[:, kv_of]         # (S, Hq, Dh)
    kpos = jnp.arange(S)

    def block(args):
        qb, qpos = args                                     # (Bq, Hq, Dh)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        seen = kpos[None, :] <= qpos[:, None]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, v)

    nb = -(-S // QUERY_BLOCK)
    pad = nb * QUERY_BLOCK - S
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        nb, QUERY_BLOCK, n_heads, Dh)
    # (a padded query row sees every key: finite, and cut off below)
    qpos = jnp.pad(kpos, (0, pad), constant_values=S).reshape(nb, QUERY_BLOCK)
    o = jax.lax.map(block, (qb, qpos)).reshape(nb * QUERY_BLOCK, d)[:S]
    return act(act(o) @ _f32(p["o_proj"]["kernel"]))


@functools.partial(jax.jit, static_argnames=(
    "what", "n_heads", "n_kv_heads", "mamba_heads", "d_state", "eps",
    "scale", "residual", "rounded"))
def layer(x, p, *, what, n_heads, n_kv_heads, mamba_heads, d_state, eps,
          scale, residual, rounded=0):
    """One layer of kind `what` over one sequence x (S, d) -> x."""
    with jax.default_matmul_precision(PRECISION):
        stored = lambda a: _round(a, rounded, 1)  # noqa: E731
        res = lambda a: _round(a, rounded, 2)  # noqa: E731
        act = lambda a: _round(a, rounded, 3)  # noqa: E731
        h = act(_rms_norm(x, p["input_norm"]["scale"], eps))
        if what == "mamba":
            out = _mamba(h, p["mamba"], act, stored, heads=mamba_heads,
                         d_state=d_state, eps=eps)
        else:
            out = _attention(h, p["attn"], act, stored, n_heads=n_heads,
                             n_kv_heads=n_kv_heads, scale=scale)
        x = res(x + residual * out)
        h = act(_rms_norm(x, p["post_norm"]["scale"], eps))
        return res(x + residual * _mlp(h, p["mlp"], act))


@functools.partial(jax.jit, static_argnames=("eps", "divide", "rounded"))
def head(x, norm, table, *, eps, divide, rounded=0):
    with jax.default_matmul_precision(PRECISION):
        x = _round(_rms_norm(x, norm["scale"], eps), rounded, 3)
        return _round(x @ _f32(table).T / divide, rounded, 3)


def hidden_states(params: dict, sizes: dict, tokens,
                  rounded: int = 0) -> jax.Array:
    """Final hidden states (S, d) of one sequence of token ids."""
    p = params["params"]
    x = _f32(p["embed"]["embedding"][jnp.asarray(tokens)]) \
        * float(sizes["embedding_multiplier"])
    args = dict(n_heads=sizes["num_attention_heads"],
                n_kv_heads=sizes["num_key_value_heads"],
                mamba_heads=sizes["mamba_n_heads"],
                d_state=sizes["mamba_d_state"],
                eps=float(sizes["rms_norm_eps"]),
                scale=float(sizes["attention_multiplier"]),
                residual=float(sizes["residual_multiplier"]))
    for i, what in enumerate(sizes["layer_types"]):
        x = layer(x, p[f"layers_{i}"], what=what, rounded=rounded, **args)
    return x


def logits(params: dict, sizes: dict, tokens, rows=None,
           rounded: int = 0) -> jax.Array:
    """Float32 logits of one sequence, at `rows` (all positions if None)."""
    if not sizes["tie_word_embeddings"]:
        raise ValueError("the family ties its head to the embedding")
    p = params["params"]
    x = hidden_states(params, sizes, tokens, rounded)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    table = p["embed"]["embedding"]
    step = -(-table.shape[0] // VOCAB_SLICES)
    return jnp.concatenate(
        [head(x, p["norm"], table[i: i + step],
              eps=float(sizes["rms_norm_eps"]),
              divide=float(sizes["logits_scaling"]), rounded=rounded)
         for i in range(0, table.shape[0], step)], axis=-1)


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
