"""Plain reference of the `sambay` family (Phi-4-mini-flash-reasoning): the
published equations in `jax.numpy`, float32 at "highest" matmul precision,
one sequence at a time, every layer at every position: no cache, no ring,
no pages, no kernels, no chunked scan, and the state-space layers as a
sequential `lax.scan` over time.

With d the hidden size, L layers and every layer
`x += Mix_i(LN(x)); x += MLP(LN(x))`, `MLP(h) = (u * silu(g)) W_down`:

  i even, i <= L/2   Mamba-1 (Gu & Dao 2023): [u, z] = h W_in;
                     u = silu(conv4(u) + b); [r, B, C] = u W_x;
                     D_t = softplus(r W_dt + b_dt); A = -exp(A_log);
                     s_t = exp(D_t A) * s_{t-1} + (D_t u_t) (x) B_t;
                     y_t = s_t C_t + D * u_t; out = (y * silu(z)) W_out.
                     Layer L/2's y is the memory m.
  i odd,  i <  L/2   differential attention (Ye et al. 2024), a query
                     sees the last `sliding_window` keys, its own included
  i = L/2 + 1        the same, full causal; its K, V serve the layers below
  i even, i > L/2+1  GMU(h, m) = (m * silu(h W_1)) W_2
  i odd,  i > L/2+1  differential cross-attention: its own W_q, W_o;
                     K and V are layer L/2+1's

Differential attention, as written: heads of 64; query heads (2p, 2p+1)
are pair p = (q1, q2), KV heads (2j, 2j+1) are pair j = (k1, k2; v1, v2),
pair p reads KV pair p // 2 (two query pairs a KV pair):
A1 = softmax(q1 k1' / 8 + mask), A2 = softmax(q2 k2' / 8 + mask),
o = (A1 - lambda A2) [v1; v2], o = RMSNorm_128(o) * (1 - lambda_init),
lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
lambda_init = 0.8 - 0.6 exp(-0.3 i).  The system computes the same with
heads of 128 and zero-padded queries; this file does not.

Departures from the published code, all arithmetic-neutral or stated in
the configuration file's `assumed`: gate and up projections are read as
two matrices (the system keeps them so; the same products); RMSNorm_128
has the learned scale the checkpoint calls `subln`; attention is computed
a block of query rows at a time, and the feed-forward a block of rows at a
time, only so that 17k positions fit beside a serving engine (the rows do
not interact); the head is multiplied a slice of the vocabulary at a time.

Weights come from the system under test (its own tree, upcast a layer at a
time).  `rounded` makes the same pass with the roundings a bfloat16 server
makes, one more at each level (ROUNDINGS), as `reference/dense_decoder.py`
does; products still accumulate in float32 at "highest".
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

PRECISION = "highest"
ROUNDINGS = (
    "none: float32 throughout",
    "K and V of every attention layer: a bf16 server stores them in its "
    "rings and pages (the scan state stays float32: the program keeps it so)",
    "and the residual stream after each add: it is held in bf16 between "
    "layers",
    "and every norm, projection, conv, scan, attention, gate and "
    "feed-forward output, the memory and the logits: the whole served type",
)
QUERY_BLOCK = 128        # rows of attention scores in flight
ROW_BLOCKS = 8           # the feed-forward runs this many blocks of rows
VOCAB_SLICES = 8


def _round(a, rounded: int, level: int):
    if rounded >= level:
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    return a


def _f32(a):
    return a.astype(jnp.float32)


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * _f32(p["scale"]) \
        + _f32(p["bias"])


def kind(i: int, n_layers: int) -> str:
    half = n_layers // 2
    if i <= half:
        return "window" if i % 2 else "mamba"
    if i == half + 1:
        return "full"
    return "cross" if i % 2 else "gmu"


def _in_row_blocks(f, x, blocks: int):
    """f over `blocks` blocks of x's rows, one at a time."""
    S = x.shape[0]
    size = -(-S // blocks)
    padded = jnp.pad(x, ((0, blocks * size - S), (0, 0)))
    out = jax.lax.map(f, padded.reshape(blocks, size, -1))
    return out.reshape(blocks * size, -1)[:S]


def _mlp(h, p, act):
    def rows(h):
        gate = act(h @ _f32(p["gate_proj"]["kernel"]))
        up = act(h @ _f32(p["up_proj"]["kernel"]))
        return act(act(up * jax.nn.silu(gate))
                   @ _f32(p["down_proj"]["kernel"]))

    return _in_row_blocks(rows, h, ROW_BLOCKS)


def _mamba(h, p, act, *, d_state, dt_rank):
    """Returns (the layer's output, y before the gate)."""
    S = h.shape[0]
    uz = act(h @ _f32(p["in_proj"]["kernel"]))
    u, z = jnp.split(uz, 2, axis=-1)
    w = _f32(p["conv_w"])                                   # (K, E)
    K = w.shape[0]
    u_pad = jnp.pad(u, ((K - 1, 0), (0, 0)))
    u = sum(u_pad[i: i + S] * w[i] for i in range(K)) + _f32(p["conv_b"])
    u = act(jax.nn.silu(u))
    rbc = act(u @ _f32(p["x_proj"]["kernel"]))
    r, b_sel, c_sel = jnp.split(rbc, [dt_rank, dt_rank + d_state], axis=-1)
    delta = jax.nn.softplus(r @ _f32(p["dt_proj"]["kernel"])
                            + _f32(p["dt_proj"]["bias"]))
    a = -jnp.exp(_f32(p["a_log"]))                          # (E, N)

    def step(s, xs):
        d, x, b, c = xs
        s = jnp.exp(d[:, None] * a) * s + (d * x)[:, None] * b[None, :]
        return s, s @ c

    _, y = jax.lax.scan(step, jnp.zeros(a.shape, jnp.float32),
                        (delta, u, b_sel, c_sel))
    y = act(y + _f32(p["d_skip"]) * u)
    return act(act(y * jax.nn.silu(z)) @ _f32(p["out_proj"]["kernel"])), y


def _diff_attention(q, k, v, p, act, *, lam_init, window, eps):
    """q (S, Hq, 64), k and v (T, Hkv, 64) with T == S, causal; a query
    sees its last `window` keys (all of them where None)."""
    S, Hq, Dh = q.shape
    Hkv = k.shape[1]
    lam = (jnp.exp(jnp.sum(_f32(p["lambda_q1"]) * _f32(p["lambda_k1"])))
           - jnp.exp(jnp.sum(_f32(p["lambda_q2"]) * _f32(p["lambda_k2"])))
           + lam_init)
    # pair p of queries reads pair p // 2 of keys and values
    pairs = Hq // 2
    kv_of = jnp.arange(pairs) // (pairs // (Hkv // 2))
    k = k.reshape(-1, Hkv // 2, 2, Dh)[:, kv_of]            # (T, pairs, 2, Dh)
    v = v.reshape(-1, Hkv // 2, 2 * Dh)[:, kv_of]           # [v1; v2]
    q = q.reshape(S, pairs, 2, Dh)
    kpos = jnp.arange(S)

    def block(args):
        qb, qpos = args                                     # (Bq, pairs, 2, Dh)
        s = jnp.einsum("qprd,kprd->prqk", qb, k) / math.sqrt(Dh)
        seen = kpos[None, :] <= qpos[:, None]
        if window is not None:
            seen &= kpos[None, :] > qpos[:, None] - window
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        # (A1 - lambda A2) [v1; v2], as two products: a server holds each
        # in its own type before it subtracts
        both = act(jnp.einsum("prqk,kpd->rqpd", a, v))
        return both[0] - lam * both[1]

    nb = -(-S // QUERY_BLOCK)
    pad = nb * QUERY_BLOCK - S
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
        nb, QUERY_BLOCK, pairs, 2, Dh)
    # (a padded query row sees every key: finite, and cut off below)
    qpos = jnp.pad(kpos, (0, pad), constant_values=S).reshape(nb, QUERY_BLOCK)
    o = jax.lax.map(block, (qb, qpos)).reshape(nb * QUERY_BLOCK, pairs,
                                               2 * Dh)[:S]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * _f32(p["subln"])
    return act(act(o * (1.0 - lam_init)).reshape(S, pairs * 2 * Dh)
               @ _f32(p["o_proj"]["kernel"]))


@functools.partial(jax.jit, static_argnames=(
    "what", "n_heads", "n_kv_heads", "window", "eps", "d_state", "dt_rank",
    "rounded"))
def layer(x, memory, cache, p, lam_init, *, what, n_heads, n_kv_heads,
          window, eps, d_state, dt_rank, rounded=0):
    """One layer of kind `what` over one sequence x (S, d) -> (x, y,
    cache): y is a Mamba layer's scan output (the caller keeps the memory
    layer's), the cache is replaced by the full-attention layer.  (One
    compiled program a kind: `lam_init` is an argument.)"""
    with jax.default_matmul_precision(PRECISION):
        kv = lambda a: _round(a, rounded, 1)  # noqa: E731
        res = lambda a: _round(a, rounded, 2)  # noqa: E731
        act = lambda a: _round(a, rounded, 3)  # noqa: E731
        S, d = x.shape
        Dh = d // n_heads
        h = act(_layer_norm(x, p["input_norm"], eps))
        if what == "mamba":
            out, memory = _mamba(h, p["mamba"], act, d_state=d_state,
                                 dt_rank=dt_rank)
        elif what == "gmu":
            g = p["gmu"]
            gate = act(h @ _f32(g["in_proj"]["kernel"]))
            out = act(act(memory * jax.nn.silu(gate))
                      @ _f32(g["out_proj"]["kernel"]))
        else:
            a = p["attn"]
            if what == "cross":
                q = act(h @ _f32(a["q_proj"]["kernel"]))
                k, v = cache
            else:
                qkv = h @ _f32(a["qkv_proj"]["kernel"])
                q, k, v = jnp.split(
                    qkv, [n_heads * Dh, (n_heads + n_kv_heads) * Dh], -1)
                q, k, v = act(q), kv(act(k)), kv(act(v))
                k = k.reshape(S, n_kv_heads, Dh)
                v = v.reshape(S, n_kv_heads, Dh)
                if what == "full":
                    cache = (k, v)
            out = _diff_attention(
                q.reshape(S, n_heads, Dh), k, v, a, act, lam_init=lam_init,
                window=window if what == "window" else None, eps=eps)
        x = res(x + out)
        h = act(_layer_norm(x, p["post_norm"], eps))
        return res(x + _mlp(h, p["mlp"], act)), memory, cache


@functools.partial(jax.jit, static_argnames=("eps", "rounded"))
def head(x, norm, table, *, eps, rounded=0):
    with jax.default_matmul_precision(PRECISION):
        x = _round(_layer_norm(x, norm, eps), rounded, 3)
        return _round(x @ _f32(table).T, rounded, 3)


def _model_args(sizes: dict) -> dict:
    return dict(n_heads=sizes["num_attention_heads"],
                n_kv_heads=sizes["num_key_value_heads"],
                window=sizes["sliding_window"],
                eps=float(sizes["layer_norm_eps"]),
                d_state=sizes["mamba_d_state"],
                dt_rank=sizes["mamba_dt_rank"])


def hidden_states(params: dict, sizes: dict, tokens,
                  rounded: int = 0) -> jax.Array:
    """Final hidden states (S, d) of one sequence of token ids."""
    p = params["params"]
    x = _f32(p["embed"]["embedding"][jnp.asarray(tokens)])
    S = x.shape[0]
    args = _model_args(sizes)
    Dh = sizes["hidden_size"] // sizes["num_attention_heads"]
    memory = jnp.zeros((S, sizes["mamba_expand"] * sizes["hidden_size"]),
                       jnp.float32)
    kv = jnp.zeros((S, sizes["num_key_value_heads"], Dh), jnp.float32)
    cache = (kv, kv)
    L = sizes["num_hidden_layers"]
    for i in range(L):
        x, y, cache = layer(x, memory, cache, p[f"layers_{i}"],
                            0.8 - 0.6 * math.exp(-0.3 * i),
                            what=kind(i, L), rounded=rounded, **args)
        if i == L // 2:
            memory = y
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
              eps=float(sizes["layer_norm_eps"]), rounded=rounded)
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
