"""Plain reference of the `mla_moe` family (the DeepSeek-V3 decoder, as
Kimi-VL-A3B-Instruct's language model has it): the published equations in
`jax.numpy`, float32 at "highest" matmul precision, one sequence at a time,
every layer at every position: no cache, no pages, no kernels, NO
absorption (a head's key and value are decompressed from the latent at
every position, as the description has it), no sort and no grouped product.
The routed feed-forward runs EACH expert in turn over ALL rows and weighs
its result by the gates, as `reference/lfm2_moe.py` does, so that it is
independent of the program's dispatch.

    h = E[token]
    each layer i:  h += Attn_i(RMSNorm(h));  h += FF_i(RMSNorm(h))
    logits = W_head RMSNorm(h)                  a head of its own

  attention   q = W_q n, a head [q_nope 128 | q_rope 64] (no query
              compression); [c | k_r] = W_kva n (512 + 64); c' =
              RMSNorm(c) with a learned scale and eps 1e-6 (the published
              code's, not rms_norm_eps); the rotary term over the 64
              of q_rope and of k_r, pairs (2i, 2i + 1) turned by position /
              theta^(2i/64) (`rope_interleave`), k_r ONE key for all heads;
              [k_nope | v]_h = W_kvb,h c'; scores (q_nope . k_nope +
              R(q_rope) . R(k_r)) / sqrt(192); causal; W_o over the heads'
              128-wide outputs
  dense FF    W2 (silu(W1 u) * W3 u), width intermediate_size (layers before
              first_k_dense_replace)
  routed FF   s = sigmoid(W_r u); S = top-k of (s + bias); g_e =
              routed_scaling_factor * s_e / (sum_S s + 1e-20);
              FF = sum_{e in S} g_e E_e(u) + Shared(u), the shared experts
              ONE gated feed-forward of n_shared_experts x the width

Departures from the published code, all arithmetic-neutral: W1 and W3 are
read from one matrix and W_kvb as (latent, head, [k_nope | v]) (the system
keeps them so; the same products); the rotated values stay where they were
(the published code moves the evens before the odds in q and k alike: a
score sums over pairs, whatever their order); attention is computed a block
of query rows at a time, the head a slice of the vocabulary at a time, and
an expert's matrices are upcast one expert at a time, only so that a
9,280-token sample fits beside an 11.8-GB serving engine.

Weights come from the system under test (its own tree).  `rounded_logits`
makes the same pass with the roundings this configuration's server makes,
one more at each level (ROUNDINGS); the last level before the routing pass
(SERVED) is the whole of what the server rounds.  A router turns a rounding
into ANOTHER EXPERT, and an exchanged expert is a jump, not a rounding (it
moves a pair of logits by 0.2), so `logits(rounded=level)` hands the
harness every level in the form `reference/lfm2_moe.py` gives its routing
pass: the float32 logits, in which the level's best token stands as far
OVER the float32 best as it lies under it (`standing_of_the_other`): a
position is set aside exactly where a sound engine that rounds as the level
does would be refused.  The routing pass runs beside the SERVED pass, takes
the other set where THAT pass has the last chosen and the first unchosen
score within ROUTING_TIE, and is handed back in the same form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISION = "highest"
# Where the k-th and the (k+1)-th of a token's biased scores lie closer
# than this IN THE SERVED PASS, a sound engine may take the other expert:
# its scores differ from that pass's by what the pass cannot reproduce (a
# rounding that falls to the other neighbour because the value rounded
# differs in its last bits; past 1,024 keys the flash kernel's softmax
# weights; the order of sums; and, downstream of a prompt position where
# the engine already took another expert, that position's other latent).
# What the bfloat16 rows and the prefill's bfloat16 q, k, v, weights and
# outputs do is IN the served pass.  Read on the chip at W_o 0.005 (my
# chip run, PR 45, `tools/mla_moe_faults.py probe`: 1,024 positions of 64
# scores in six routed layers, first to last layer): the program made to
# take the served pass's experts lay 1.2e-4 ... 2.36e-4 from its scores at
# their widest at prompt positions past 448 tokens (rms 2.5 ... 5.3e-5),
# 2.2e-5 ... 6.0e-5 at decode positions (rms 0.5 ... 1.35e-5); left to
# itself it first departed from the served pass, at a judged position, at
# margins up to 4.7e-4 (9 such positions; twice the widest difference: a
# selection falls when the DIFFERENCE of two scores' errors passes its
# margin).  Both grow with W_o (against the float32 pass: 1.52e-4, 3.9e-4,
# 7.3-9.2e-4 at 0.002, 0.005, 0.01), so at the committed 0.002 they are
# 0.95e-4, 2.4e-5 and 1.9e-4: the tie leaves a third over the widest
# departure and is ten times the widest difference at a decode position,
# which all but a request's first judged position are.  A wider one costs
# positions set aside (the harness caps them at one in ten: 2.5e-4 set
# 3.6-6.3% of a sample aside in the final runs; at W_o 0.005 the 7.1e-4
# its departures need set 10.4% aside and the cap refused the sound
# engine: README-mla-moe.md).
ROUTING_TIE = 2.5e-4
ROUNDINGS = (
    "none: float32 throughout",
    "the latent and the rotated key of every layer: a bf16 server stores "
    "them in its pages",
    "and every activation that enters a product with a weight, cut to two "
    "bfloat16 terms (16 bits), as the server's products take it (the "
    "residual stream, norms, softmax and the router stay float32)",
    "and, at the positions of the prompt (a prefill's: up to the first "
    "position that produced a token), what the server hands the flash "
    "kernel and takes from it in bfloat16: q, k_nope and v a head, the "
    "softmax's weights exp(s - max) where they multiply v (their sum stays "
    "float32), and the attention's output; a generated position's query "
    "stays float32 over the cached rows, as the absorbed decode has it "
    "(past 1,024 keys the kernel rounds exp(s - a block's running "
    "maximum), another number of the same size)",
    "the served pass again, and beside it one that takes, at every router "
    "selection whose last chosen and first unchosen biased scores lie "
    "within ROUTING_TIE of each other IN THE SERVED PASS, the set the "
    "served pass did not take",
)
ROUTING_PASS = len(ROUNDINGS) - 1
SERVED = ROUTING_PASS - 1
# The latent's norm is built with the norm class's default eps in the
# published code (`kv_a_layernorm = RMSNorm(kv_lora_rank)`), every other
# norm with the configuration's `rms_norm_eps`.
LATENT_NORM_EPS = 1e-6
QUERY_BLOCK = 128        # rows of attention scores in flight
VOCAB_SLICES = 8


def _bf16(a):
    """`a` rounded to bfloat16 (to nearest, ties to even) and back, ON ITS
    BITS.  Not `a.astype(bfloat16).astype(float32)`: the chip's compiler is
    allowed to keep the excess precision of such a round trip and does
    (`models/sambay._two_terms`, PR 28; this PR's first reference rounded
    so, and on the chip its rounded passes lay as far from the program as
    the float32 pass did: README-mla-moe.md)."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _stored(a, rounded: int):
    """What a page holds: bfloat16."""
    return _bf16(a) if rounded >= 1 else a


def _entering(a, rounded: int):
    """An activation as a product with a weight takes it: its leading 16
    bits, as two bfloat16 terms hold them."""
    if rounded < 2:
        return a

    def leading(v):     # (bit masks: a round trip through bfloat16 is
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)  # the compiler's
        return jax.lax.bitcast_convert_type(                # to elide)
            bits & jnp.uint32(0xFFFF0000), jnp.float32)

    hi = leading(a)
    return hi + leading(a - hi)


def _f32(a):
    return a.astype(jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(scale)


def _rotate_pairs(a, theta):
    """a (S, ..., d): each pair (a[2i], a[2i+1]) turned by position /
    theta^(2i/d), in place."""
    S, d = a.shape[0], a.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    angle = angle.reshape((S,) + (1,) * (a.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    even, odd = a[..., 0::2], a[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(a.shape)


def _attention(h, p, act, stored, prompt, *, n_heads, kv_rank, d_nope,
               d_rope, theta):
    """`prompt`: None, or the number of leading positions whose attention
    went through the flash kernel in bfloat16 (level SERVED)."""
    S, _ = h.shape
    u = act(h)
    q = (u @ _f32(p["q_proj"]["kernel"])).reshape(S, n_heads, d_nope + d_rope)
    q_nope, q_rope = q[..., :d_nope], _rotate_pairs(q[..., d_nope:], theta)
    kv = u @ _f32(p["kv_a_proj"]["kernel"])
    latent = stored(_rms_norm(kv[:, :kv_rank], p["kv_norm"]["scale"],
                              LATENT_NORM_EPS))
    k_rope = stored(_rotate_pairs(kv[:, kv_rank:], theta))    # (S, d_rope)
    w = _f32(p["kv_b"])                 # (latent, head, [k_nope | v])
    decompressed = jnp.einsum("sc,chn->shn", act(latent), w)
    k_nope, v = decompressed[..., :d_nope], decompressed[..., d_nope:]
    kpos = jnp.arange(S)
    scale = 1.0 / jnp.sqrt(float(d_nope + d_rope))

    def attend(qn, qr, kn, vv, qpos, weights=lambda w: w):
        s = (jnp.einsum("qhd,khd->hqk", qn, kn)
             + jnp.einsum("qhd,kd->hqk", qr, k_rope)) * scale
        seen = kpos[None, :] <= qpos[:, None]
        s = jnp.where(seen, s, -jnp.inf)
        w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        return jnp.einsum("hqk,khd->qhd", weights(w), vv) \
            / jnp.sum(w, axis=-1)[..., None].transpose(1, 0, 2)

    handed = None if prompt is None else (_bf16(k_nope), _bf16(v))

    def block(args):
        qn, qr, qpos = args                                 # (Bq, H, .)
        o = attend(qn, qr, k_nope, v, qpos)
        if handed is None:
            return o
        # (the rotated key is bfloat16 already: the cache's)
        through = _bf16(attend(_bf16(qn), _bf16(qr), *handed, qpos, _bf16))
        return jnp.where((qpos < prompt)[:, None, None], through, o)

    nb = -(-S // QUERY_BLOCK)
    pad = nb * QUERY_BLOCK - S
    blocks = lambda a: jnp.pad(  # noqa: E731
        a, ((0, pad), (0, 0), (0, 0))).reshape(nb, QUERY_BLOCK, *a.shape[1:])
    # (a padded query row sees every key: finite, and cut off below)
    qpos = jnp.pad(kpos, (0, pad), constant_values=S).reshape(nb, QUERY_BLOCK)
    o = jax.lax.map(block, (blocks(q_nope), blocks(q_rope), qpos))
    o = o.reshape(nb * QUERY_BLOCK, -1)[:S]
    return act(o) @ _f32(p["o_proj"]["kernel"])


def _gated_ffn(h, p, act):
    a, b = jnp.split(act(h) @ _f32(p["w13"]["kernel"]), 2, axis=-1)
    return act(jax.nn.silu(a) * b) @ _f32(p["w2"]["kernel"])


def _gates(h, p, *, top_k, scaling, other=None):
    """(S, E) float32: each token's gate at the experts it chose, zero
    elsewhere; its biased scores (what chose); and its tie: (whether the
    last chosen and the first unchosen score lie within ROUTING_TIE, the
    set with the first unchosen expert in the last one's place).  `other`
    is the tie of ANOTHER pass over the same tokens: where that pass was
    near a tie, the set it did not take is taken here."""
    s = jax.nn.sigmoid(h @ _f32(p["router"]))
    biased = s + _f32(p["expert_bias"])
    top, idx = jax.lax.top_k(biased, top_k + 1)
    chosen = idx[:, :top_k]
    tie = (top[:, top_k - 1] - top[:, top_k] < ROUTING_TIE,
           chosen.at[:, top_k - 1].set(idx[:, top_k]))
    if other is not None:
        chosen = jnp.where(other[0][:, None], other[1], chosen)
    g = s * jnp.sum(jax.nn.one_hot(chosen, s.shape[-1]), axis=1)
    return scaling * g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20), \
        biased, tie


def _routed_ffn(h, p, act, *, top_k, scaling, other):
    g, biased, tie = _gates(h, p, top_k=top_k, scaling=scaling, other=other)
    u = act(h)

    def one(total, e):                  # every row through expert e
        a, b = jnp.split(u @ _f32(p["w13"][e]), 2, axis=-1)
        y = act(jax.nn.silu(a) * b) @ _f32(p["w2"][e])
        return total + jax.lax.dynamic_slice_in_dim(g, e, 1, axis=1) * y, None

    total, _ = jax.lax.scan(one, jnp.zeros_like(h),
                            jnp.arange(p["w13"].shape[0]))
    return total, biased, tie


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "kv_rank", "d_nope", "d_rope", "theta", "eps", "top_k",
    "scaling", "rounded"))
def layer(x, p, *, n_heads, kv_rank, d_nope, d_rope, theta, eps, top_k,
          scaling, rounded=0, other=None, prompt=None):
    """One layer over one sequence x (S, d) -> (x, the router's biased
    scores (S, E), its tie (`_gates`)); the last two None in a dense
    layer.  `rounded`: a level of ROUNDINGS up to SERVED; `prompt`: the
    positions a prefill made (read at SERVED only; all of them if None);
    `other`: the tie of the pass this one runs beside, whose other set is
    taken where it was near one (the routing pass)."""
    with jax.default_matmul_precision(PRECISION):
        stored = lambda a: _stored(a, rounded)  # noqa: E731
        act = lambda a: _entering(a, rounded)  # noqa: E731
        if rounded < SERVED:
            prompt = None
        elif prompt is None:
            prompt = x.shape[0]
        h = _rms_norm(x, p["attn_norm"]["scale"], eps)
        x = x + _attention(h, p["attn"], act, stored, prompt,
                           n_heads=n_heads, kv_rank=kv_rank, d_nope=d_nope,
                           d_rope=d_rope, theta=theta)
        h = _rms_norm(x, p["ffn_norm"]["scale"], eps)
        if "mlp" in p:
            return x + _gated_ffn(h, p["mlp"], act), None, None
        out, biased, tie = _routed_ffn(h, p["experts"], act, top_k=top_k,
                                       scaling=scaling, other=other)
        return x + out + _gated_ffn(h, p["shared"], act), biased, tie


@functools.partial(jax.jit, static_argnames=("eps", "rounded"))
def head(x, norm, table, *, eps, rounded=0):
    with jax.default_matmul_precision(PRECISION):
        x = _entering(_rms_norm(x, norm["scale"], eps), rounded)
        return x @ _f32(table)


def _streams(params: dict, sizes: dict, tokens, rounded: int,
             scores: list | None = None, prompt: int | None = None) -> tuple:
    """Final hidden states (S, d) of the pass `rounded` (the routing pass:
    of the pass that takes the served pass's other sets), and the level
    its head rounds at.  `prompt`: the leading positions a prefill made
    (all of them if None); each routed layer's biased scores are appended
    to `scores` where given."""
    p = params["params"]
    x = _f32(p["embed"]["embedding"][jnp.asarray(tokens)])
    args = dict(n_heads=sizes["num_attention_heads"],
                kv_rank=sizes["kv_lora_rank"],
                d_nope=sizes["qk_nope_head_dim"],
                d_rope=sizes["qk_rope_head_dim"],
                theta=float(sizes["rope_theta"]),
                eps=float(sizes["rms_norm_eps"]),
                top_k=sizes["num_experts_per_tok"],
                scaling=float(sizes["routed_scaling_factor"]),
                prompt=prompt)
    routing = rounded == ROUTING_PASS
    level = SERVED if routing else rounded
    served = x if routing else None
    for i in range(sizes["num_hidden_layers"]):
        tie = None
        if routing:
            served, _, tie = layer(served, p[f"layers_{i}"], rounded=level,
                                   **args)
        x, biased, _ = layer(x, p[f"layers_{i}"], other=tie, rounded=level,
                             **args)
        if scores is not None and biased is not None:
            scores.append(biased)
    return x, level


def hidden_states(params: dict, sizes: dict, tokens, rounded: int = 0,
                  scores: list | None = None,
                  prompt: int | None = None) -> jax.Array:
    """Final hidden states (S, d) of one sequence of token ids; each
    routed layer's biased scores are appended to `scores` where given."""
    return _streams(params, sizes, tokens, rounded, scores, prompt)[0]


def _head(params: dict, sizes: dict, x, rows, rounded: int):
    p = params["params"]
    if rows is not None:
        x = x[jnp.asarray(rows)]
    table = p["lm_head"]["kernel"]                          # (d, V)
    step = -(-table.shape[1] // VOCAB_SLICES)
    return jnp.concatenate(
        [head(x, p["norm"], table[:, i: i + step],
              eps=float(sizes["rms_norm_eps"]), rounded=rounded)
         for i in range(0, table.shape[1], step)], axis=-1)


def standing_of_the_other(sound, other):
    """A pass as the harness reads a level (how far the reference's own
    best token falls under the level's best): the float32 logits `sound`
    (S, V), in which the best token of the pass `other` is set as far over
    the float32 best as it lies under it.  Where both have the same best
    token nothing moves."""
    at = jnp.arange(sound.shape[0])
    token = jnp.argmax(other, axis=-1)
    best = jnp.max(sound, axis=-1)
    return sound.at[at, token].set(2.0 * best - sound[at, token])


def rounded_logits(params: dict, sizes: dict, tokens, rows=None,
                   rounded: int = 0, prompt: int | None = None) -> jax.Array:
    """The logits of the pass `rounded` itself, float32, at `rows` (all
    positions if None)."""
    x, level = _streams(params, sizes, tokens, rounded, prompt=prompt)
    return _head(params, sizes, x, rows, level)


def logits(params: dict, sizes: dict, tokens, rows=None,
           rounded: int = 0) -> jax.Array:
    """Float32 logits of one sequence at `rows` (all positions if None);
    for a level of ROUNDINGS, those logits with the level's best token
    standing over them (`standing_of_the_other`).  `rows` are the
    positions that produced tokens, as the harness asks for them: the
    first is the prompt's last, and the positions up to it are the ones a
    prefill made (every position where `rows` is None)."""
    sound = rounded_logits(params, sizes, tokens, rows)
    if not rounded:
        return sound
    prompt = None if rows is None else int(rows[0]) + 1
    return standing_of_the_other(sound, rounded_logits(
        params, sizes, tokens, rows, rounded, prompt))


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
