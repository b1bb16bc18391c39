"""Plain reference of the `minicpm_sala` family (MiniCPM-SALA): the
equations in `jax.numpy`, float32 at "highest" matmul precision, one
sequence at a time, every layer at every position: no cache, no pages, no
kernels, no chunked matrix form.  Written from the equations below, not
from `ray_tpu/models/minicpm_sala.py`.

    h = scale_emb * E[token]
    each layer i, by mixer_types[i], with r = scale_depth / sqrt(L_published):
        h += r * Mixer_i(RMSNorm(h))
        h += r * W2 (silu(a) * b),  (a, b) = split(W13 RMSNorm(h))
    logits = (RMSNorm(h) / (hidden_size / dim_model_base)) W_head

  lightning-attn   [q | k | v | g] = W_in x, H heads of D each; q and k
              RMS-normed a head and rotated (halves, theta); head i = 1..H
              decays by lambda_i = exp(-2^(-8 i / H)); the recurrence runs
              TOKEN BY TOKEN (a `lax.scan` over positions):
                  S_t = lambda_i S_{t-1} + k_t^T v_t,  o_t = q_t S_t / sqrt(D)
              out = W_o (sigmoid(g) * RMSNorm_head(o))
  minicpm4    [q | k | v | g] = W_in x, Hq query heads over Hkv K/V heads of
              D; q and k RMS-normed a head; no position term.  A query at
              position t of a sequence whose PROMPT was P tokens long is
              `sparse` when P > dense_len or t >= dense_len (the sequence,
              this token counted, is past dense_len), else it attends
              causally over everything.  A sparse query keeps BLOCKS of
              block_size tokens, for each K/V head its own:
                compressed key j = mean of the keys of tokens [stride j,
                  stride j + kernel), visible once stride j + kernel - 1 <= t
                w = softmax over the visible j of q . c_j / sqrt(D), a head;
                  summed over the heads that share the K/V head
                score of block b = max of w_j over the visible windows j
                  that overlap block b (computed from the windows' and the
                  blocks' first and last tokens)
                kept: the init_blocks first blocks; the window_size /
                  block_size blocks that end with the query's own; of the
                  rest that lie before the window, the topk of highest
                  score (ranked by a stable sort; all, if they are fewer)
              and attends, causally, over the tokens of the kept blocks.
              out = W_o (sigmoid(g) * attention)

The prompt's length is not among `logits`' arguments as the harness calls
it (`logits(params, sizes, tokens, rows, rounded)`): it is taken from the
rows asked for, whose first is the prompt's last token (`prompt_len`
says it outright).

Departures from a straight transcription, all arithmetic-neutral: the
sparse layers run a block of query rows at a time and the feed-forward a
block of rows at a time, so that 33,280 tokens fit beside a serving engine
(rows do not interact).

Weights come from the system under test (its own tree, upcast a layer at a
time).  `rounded` makes the same pass with the roundings a bfloat16 server
makes, one more at each level (ROUNDINGS).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

PRECISION = "highest"
# Where the last kept and the first dropped block of a selection score
# closer than this IN THE SERVED PASS, a sound engine may keep the other:
# its scores differ from the pass's by the order of its sums and by what
# seven layers of two-term products upstream leave.  A score is a sum of 16
# softmax weights over up to 2,078 compressed keys (0.044 at the median of
# a 9,216-token prompt; the 64th and 65th block 1.4e-4 apart).  Read on the
# chip (my chip run, PR 49, `tools/minicpm_sala_faults.py selection`): the
# program's scores lie 1.1e-8 (rms) from the pass's in the stage's first
# layer and 1.3-1.9e-6 in its last; there 14-26 of 6,016 kept sets differ
# (three probes), at margins of 5.0e-7 to 3.0e-6 and one of 1.3e-5.  The tie
# covers all but that one; 9% of a 9k prompt's selections lie under it
# (more of a longer one's: 479 blocks compete at 32k), and the level sets a
# position aside only where exchanging ALL of them moves the reference's
# own best token by more than the tolerance (README-minicpm-sala.md).
SELECTION_TIE = 5e-6
ROUNDINGS = (
    "none: float32 throughout",
    "K and V of every sparse layer: a bf16 server stores them in its pages "
    "(the compressed keys are means of the keys BEFORE that rounding and "
    "the state S stays float32: the configuration keeps both so)",
    "and every activation that enters a product with a weight, cut to two "
    "bfloat16 terms (16 bits), as the server's products take it; so too, "
    "in a prompt past dense_len, the queries and the softmax's weights of "
    "the sparse layers where they multiply K and V",
    "and, in a prompt of at most dense_len, what the flash kernel is handed "
    "in bfloat16: the queries, and the softmax's weights where they "
    "multiply V",
    "the served pass again, but at every selection whose last kept and "
    "first dropped block score within SELECTION_TIE of each other the "
    "other of the two is kept",
)
TIE_PASS = len(ROUNDINGS) - 1
QUERY_BLOCK = 64         # rows of attention scores in flight
ROW_BLOCK = 2048         # rows of the feed-forward in flight
VOCAB_SLICES = 8
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def _bf16(a):
    """`a` rounded to bfloat16 (to nearest, ties to even) and back, ON ITS
    BITS (`reference/mla_moe._bf16` says why not by `astype`)."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _16_bits(a):
    """`a` as the sum of two bfloat16 terms: its leading 8 bits of mantissa
    and, rounded, what they left."""
    hi = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(a, jnp.uint32) & jnp.uint32(0xFFFF0000),
        jnp.float32)
    return hi + _bf16(a - hi)


def _f32(a):
    return a.astype(jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(scale)


def _operand(x, rounded: int):
    """An activation as it enters a product with a weight: as it is, or
    (level 2) cut to 16 bits."""
    return x if rounded < 2 else _16_bits(x)


def _in_row_blocks(f, xs, block: int):
    """f over blocks of `block` rows of every array of `xs`, in turn."""
    S = xs[0].shape[0]
    n = -(-S // block)
    cut = lambda a: jnp.pad(  # noqa: E731
        a, ((0, n * block - S),) + ((0, 0),) * (a.ndim - 1), mode="edge") \
        .reshape(n, block, *a.shape[1:])
    out = jax.lax.map(lambda args: f(*args), tuple(map(cut, xs)))
    return out.reshape(n * block, *out.shape[2:])[:S]


def _mlp(h, p, rounded):
    def rows(h):
        a, b = jnp.split(_operand(h, rounded) @ _f32(p["w13"]), 2, -1)
        return _operand(jax.nn.silu(a) * b, rounded) @ _f32(p["w2"])

    return _in_row_blocks(rows, (h,), ROW_BLOCK)


def _rotated(x, theta):
    """x (S, H, D) at positions 0..S-1: pairs (d, d + D/2) turned by
    position x inv_freq_d, inv_freq = 1 / theta^(2d/D): the published
    form (`outer(t, inv_freq)` in float32).  Past 16,384 positions a
    float32 angle of the fastest pair is known to 2e-3 only, so HOW it is
    formed is part of the model: position / theta^(2d/D) differs from it
    in the last bit, 0.4% of a cosine there (my chip run, PR 49)."""
    S, _, D = x.shape
    half = D // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / D)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def _lightning(h, p, rounded, *, heads, theta, eps):
    S = h.shape[0]
    qkvg = _in_row_blocks(
        lambda h: _operand(h, rounded) @ _f32(p["in_proj"]), (h,), ROW_BLOCK)
    q, k, v, g = jnp.split(qkvg, 4, axis=-1)
    D = q.shape[-1] // heads
    q = _rotated(_rms_norm(q.reshape(S, heads, D), p["q_norm"], eps), theta)
    k = _rotated(_rms_norm(k.reshape(S, heads, D), p["k_norm"], eps), theta)
    v = v.reshape(S, heads, D)
    lam = jnp.exp(-(2.0 ** (-8.0 * jnp.arange(1, heads + 1) / heads)))

    def step(s, xs):                    # s (H, D of k, D of v): one token
        q_t, k_t, v_t = xs
        s = lam[:, None, None] * s + k_t[:, :, None] * v_t[:, None, :]
        return s, jnp.sum(q_t[:, :, None] * s, axis=1) / math.sqrt(D)

    _, o = jax.lax.scan(step, jnp.zeros((heads, D, D), jnp.float32),
                        (q, k, v))
    o = _rms_norm(o, p["out_norm"], eps).reshape(S, heads * D)
    return _in_row_blocks(
        lambda o, g: _operand(jax.nn.sigmoid(g) * o, rounded)
        @ _f32(p["o_proj"]), (o, g), ROW_BLOCK)


def _kept(w, t, *, tokens, kernel, stride, block, topk, window, init, flip):
    """One query's kept blocks, one K/V head: w (J,) its summed softmax
    weight on each compressed key (0 where unseen), t its position in a
    sequence of `tokens` -> (nb,) bool.  Blocks and windows by their first
    and last tokens."""
    J = w.shape[0]
    nb = -(-tokens // block)
    first_w = stride * jnp.arange(J)
    last_w = first_w + kernel - 1
    first_b = block * jnp.arange(nb)
    last_b = first_b + block - 1
    overlap = (first_w[None, :] <= last_b[:, None]) \
        & (last_w[None, :] >= first_b[:, None]) \
        & (last_w[None, :] <= t)                              # (nb, J)
    score = jnp.max(jnp.where(overlap, w[None, :], 0.0), axis=1)
    own = t // block
    b = jnp.arange(nb)
    forced = (b < init) | ((b > own - window // block) & (b <= own))
    rest = (b >= init) & (b <= own - window // block)
    # rank: how many competing blocks stand before this one (a higher
    # score, or the same score and a lower index)
    s = jnp.where(rest, score, -1.0)
    before = (s[None, :] > s[:, None]) | \
        ((s[None, :] == s[:, None]) & (b[None, :] < b[:, None]))
    rank = jnp.sum(before & rest[None, :], axis=1)
    chosen = rest & (rank < topk)
    if flip:
        # the last kept and the first dropped, where they all but tie
        last = jnp.max(jnp.where(rest & (rank == topk - 1), s, -1.0))
        nxt = jnp.max(jnp.where(rest & (rank == topk), s, -1.0))
        tie = (nxt >= 0.0) & (last - nxt < SELECTION_TIE)
        swapped = (chosen & (rank != topk - 1)) | (rest & (rank == topk))
        chosen = jnp.where(tie, swapped, chosen)
    return forced | chosen, s


def _sparse(h, p, prompt, prompt_len, rounded, *, heads, kv_heads, eps,
            sparse_config, tell=False):
    sc = dict(sparse_config)
    dense_len = sc.pop("dense_len")
    S = h.shape[0]
    stored = _bf16 if rounded >= 1 else (lambda a: a)
    width = p["in_proj"].shape[1]
    D = width // (2 * heads + 2 * kv_heads)
    G = heads // kv_heads
    qkvg = _in_row_blocks(
        lambda h: _operand(h, rounded) @ _f32(p["in_proj"]), (h,), ROW_BLOCK)
    q, k, v, g = jnp.split(
        qkvg, [heads * D, (heads + kv_heads) * D, (heads + 2 * kv_heads) * D],
        axis=-1)
    q = _rms_norm(q.reshape(S, kv_heads, G, D), p["q_norm"], eps)
    k = _rms_norm(k.reshape(S, kv_heads, D), p["k_norm"], eps)
    kernel, stride = sc["kernel"], sc["stride"]
    # (a sequence shorter than one window: one key, which nothing sees)
    J = max((S - kernel) // stride + 1, 1)
    members = stride * jnp.arange(J)[:, None] + jnp.arange(kernel)[None, :]
    ck = jnp.mean(k[jnp.minimum(members, S - 1)], axis=1)     # (J, Hkv, D)
    k, v = stored(k), stored(v.reshape(S, kv_heads, D))
    last_w = stride * jnp.arange(J) + kernel - 1
    kpos = jnp.arange(S)
    flip = rounded >= TIE_PASS

    def block(qb, tb, at):              # qb (Bq, Hkv, G, D), tb (Bq,)
        sparse = (tb >= dense_len) | (prompt_len > dense_len)
        s = jnp.einsum("qhgd,jhd->qhgj", qb, ck) / math.sqrt(D)
        seen = last_w[None, :] <= tb[:, None]                 # (Bq, J)
        w = jax.nn.softmax(jnp.where(seen[:, None, None], s, -1e30), -1)
        w = jnp.sum(jnp.where(seen[:, None, None], w, 0.0), axis=2)
        kept, scores = jax.vmap(jax.vmap(
            functools.partial(_kept, tokens=S, flip=flip, **sc),
            in_axes=(0, None)), in_axes=(0, 0))(w, tb)        # (Bq, Hkv, nb)
        tok = jnp.repeat(kept, sc["block"], axis=-1)[..., :S]
        seen_k = (kpos[None, :] <= tb[:, None])[:, None] \
            & (tok | ~sparse[:, None, None])                  # (Bq, Hkv, S)
        # what the program's attention over a PROMPT takes its queries
        # and its softmax's weights as: 16 bits in the masked form (level
        # 2), bfloat16 in the flash kernel (level 3); a generated
        # position's stay float32 over the pages
        cut = _16_bits if rounded >= 2 else (lambda a: a)
        if rounded >= 3:
            cut = lambda a: jnp.where(  # noqa: E731
                prompt_len > dense_len, _16_bits(a), _bf16(a))
        at = at[:, None, None, None]
        a = jnp.einsum("qhgd,khd->qhgk", jnp.where(at, cut(qb), qb), k) \
            / math.sqrt(D)
        a = jnp.where(seen_k[:, :, None], a, -jnp.inf)
        e = jnp.exp(a - jnp.max(a, axis=-1, keepdims=True))
        z = jnp.sum(e, axis=-1, keepdims=True)
        o = (jnp.einsum("qhgk,khd->qhgd", jnp.where(at, cut(e), e), v)
             / z).reshape(qb.shape[0], heads * D)
        return jnp.concatenate([o, scores.reshape(qb.shape[0], -1)], -1) \
            if tell else o

    o = _in_row_blocks(block, (q, kpos, prompt), QUERY_BLOCK)
    o, scores = o[:, : heads * D], o[:, heads * D:].reshape(S, kv_heads, -1)
    return _in_row_blocks(
        lambda o, g: _operand(jax.nn.sigmoid(g) * o, rounded)
        @ _f32(p["o_proj"]), (o, g), ROW_BLOCK), scores


@functools.partial(jax.jit, static_argnames=(
    "what", "heads", "kv_heads", "theta", "eps", "residual", "sparse_config",
    "rounded", "tell"))
def layer(x, p, prompt_len, *, what, heads, kv_heads, theta, eps, residual,
          sparse_config, rounded=0, tell=False):
    """One layer of kind `what` over one sequence x (S, d) -> (x after its
    mixer, x after its feed-forward, and where `tell` a sparse layer's
    selection: (S, Hkv, blocks) the score of every block that competed for
    a query's top-k, -1 elsewhere)."""
    with jax.default_matmul_precision(PRECISION):
        prompt = jnp.arange(x.shape[0]) < prompt_len
        h = _rms_norm(x, p["input_norm"], eps)
        if what == LIGHTNING:
            out, scores = _lightning(h, p, rounded, heads=heads, theta=theta,
                                     eps=eps), None
        else:
            out, scores = _sparse(h, p, prompt, prompt_len, rounded,
                                  heads=heads, kv_heads=kv_heads, eps=eps,
                                  sparse_config=sparse_config, tell=tell)
        mixed = x + residual * out
        h = _rms_norm(mixed, p["post_norm"], eps)
        return mixed, mixed + residual * _mlp(h, p, rounded), scores


@functools.partial(jax.jit, static_argnames=("eps", "divide", "rounded"))
def head(x, norm, table, *, eps, divide, rounded=0):
    with jax.default_matmul_precision(PRECISION):
        x = _rms_norm(x, norm, eps) / divide
        return (_16_bits(x) if rounded >= 2 else x) @ _f32(table)


def sparse_config_of(sizes: dict) -> tuple:
    s = sizes["sparse_config"]
    return tuple(sorted(dict(
        kernel=s["kernel_size"], stride=s["kernel_stride"],
        block=s["block_size"], topk=s["topk"], window=s["window_size"],
        init=s["init_blocks"], dense_len=s["dense_len"]).items()))


def streams(params: dict, sizes: dict, tokens, prompt_len: int,
            rounded: int = 0, tell: bool = False):
    """A layer at a time, the stream (S, d) of one sequence of token ids
    whose first `prompt_len` were its prompt: (before the layer, after its
    mixer, after its feed-forward, a sparse layer's selection scores where
    `tell`: see `layer`)."""
    p = params["params"]
    x = _f32(p["embed"][jnp.asarray(tokens)]) * float(sizes["scale_emb"])
    args = dict(heads=sizes["num_attention_heads"],
                kv_heads=sizes["num_key_value_heads"],
                theta=float(sizes["rope_theta"]),
                eps=float(sizes["rms_norm_eps"]),
                residual=float(sizes["scale_depth"])
                / math.sqrt(sizes["published_layers"]),
                sparse_config=sparse_config_of(sizes))
    for i, what in enumerate(sizes["mixer_types"]):
        mixed, out, scores = layer(
            x, p[f"layers_{i}"], jnp.int32(prompt_len), what=what,
            rounded=rounded, tell=tell and what == SPARSE, **args)
        yield x, mixed, out, scores
        x = out


def hidden_states(params: dict, sizes: dict, tokens, prompt_len: int,
                  rounded: int = 0) -> jax.Array:
    """Final hidden states (S, d) of that sequence."""
    for _, _, x, _ in streams(params, sizes, tokens, prompt_len, rounded):
        pass
    return x


def logits(params: dict, sizes: dict, tokens, rows=None, rounded: int = 0,
           prompt_len: int | None = None) -> jax.Array:
    """Float32 logits of one sequence, at `rows` (all positions if None).
    The prompt is the tokens up to the first of `rows` (a teacher-forced
    pass asks for the prompt's last token first), the whole sequence where
    no rows are named, or `prompt_len` tokens."""
    if prompt_len is None:
        prompt_len = len(tokens) if rows is None else int(rows[0]) + 1
    x = hidden_states(params, sizes, tokens, prompt_len, rounded)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head_logits(params, sizes, x, rounded)


def head_logits(params: dict, sizes: dict, x, rounded: int = 0) -> jax.Array:
    """The logits of final hidden states x (rows, d), the vocabulary a
    slice at a time."""
    p = params["params"]
    table = p["lm_head"]
    step = -(-table.shape[1] // VOCAB_SLICES)
    return jnp.concatenate(
        [head(x, p["norm"], table[:, i: i + step],
              eps=float(sizes["rms_norm_eps"]),
              divide=sizes["hidden_size"] / sizes["dim_model_base"],
              rounded=rounded)
         for i in range(0, table.shape[1], step)], axis=-1)


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
