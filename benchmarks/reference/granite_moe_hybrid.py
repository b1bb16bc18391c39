"""Plain reference of the `granite_moe_hybrid` family (one chip's share of
Granite-4.0-H-Small): the published equations in `jax.numpy`, float32 at
"highest" matmul precision, one sequence at a time, every layer at every
position: no cache, no pages, no kernels, no sort and no grouped product,
the Mamba-2 recurrence token by token, heads of 128 as written.  The mixers
and the head are `reference/granite_hybrid.py`'s own functions (the dense
member's reference: the same equations at other sizes); what is new here is
the feed-forward and the pass about the router.

    h = embedding_multiplier * E[token]
    each layer:  h += residual_multiplier * Mixer(RMSNorm(h))
                 u = RMSNorm(h)
                 l = W_r u                         72 logits, no bias
                 S = top-10 of l;  g = softmax(l[S])   over the ten alone
                 routed = sum_{e in S, e HELD} g_e W2_e (silu(a_e) * b_e),
                          (a_e, b_e) = split(W13_e u)
                 shared = W_out (silu(a) * b), (a, b) = split(W_in u)
                 h += residual_multiplier * (routed + shared)
    logits = RMSNorm(h) E^T / logits_scaling

The share.  The configuration holds experts `first` ... `first + count - 1`
of each layer (`sizes["experts_held"]`) and the weights handed in hold those
alone.  The router is the whole layer's (all 72 logits, the ten largest,
their softmax); the sum runs over the chosen experts that are held, and what
the absent ones would add is left out, here as in the program, and the
partial result goes on to the next layer.  Each held expert runs in turn
over ALL rows and its result is weighed by the gates (zero where the router
did not choose it): nothing of the program's dispatch is shared.

`rounded` makes the same pass with the roundings a bfloat16 server makes,
one more at each level (ROUNDINGS: `reference/granite_hybrid.py`'s three,
the state S float32 in all of them, and the router's matrix, logits, top-k
and softmax float32 in all of them, because the configuration says so);
the last level rounds nothing and is about the router alone, as
`reference/lfm2_moe.py`'s: ROUTING_TIE, `standing_of_the_other`.
"""

from __future__ import annotations

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp



def _beside(name: str):
    """A reference from the file beside this one (by path: a checkout of
    `paths` alone, or a test's copy, has no package to import)."""
    spec = importlib.util.spec_from_file_location(
        f"_bench_reference_{name}_for_granite_moe", os.path.join(
            os.path.dirname(os.path.abspath(__file__)), name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# The dense member's reference (the mixers and the head), and the routed
# family's that first had a routing pass (how a level hands it on).
dense = _beside("granite_hybrid")
standing_of_the_other = _beside("lfm2_moe").standing_of_the_other

PRECISION = dense.PRECISION
# Where the 10th and the 11th of a token's router logits lie closer than
# this, a sound engine may take the other expert: its own logits differ
# from the reference's by what its two-term products and its bfloat16 K, V
# and conv windows leave upstream.  README-granite-moe-hybrid.md has the
# readings it rests on (my chip runs, PR 52: the program's logits beside
# the reference's over whole prompts, a layer at a time).
ROUTING_TIE = 5e-3
ROUNDINGS = dense.ROUNDINGS + (
    "none of the roundings above: float32 throughout, beside the sound pass; "
    "at every router selection whose 10th and 11th logits lie within "
    "ROUTING_TIE of each other IN THE SOUND PASS, and of which at least one "
    "of the two experts is held here, the set the sound pass did not take "
    "is taken.  What comes back is the sound pass's logits, in which the "
    "token that this pass prefers stands as far OVER the sound pass's best "
    "as it lies under it there (reference/lfm2_moe.py says why)",
)
ROUTING_PASS = len(ROUNDINGS) - 1
_f32 = dense._f32


def _gates(u, router, *, top_k, held, other=None):
    """(S, E) float32: each token's gate at the experts it chose (a softmax
    over the chosen logits alone), zero elsewhere; its logits; and its tie:
    (whether the last chosen and the first unchosen logit lie within
    ROUTING_TIE and one of the two experts is held, the set with the first
    unchosen expert in the last one's place).  `other` is the tie of
    ANOTHER pass over the same tokens: where that pass was near a tie, the
    set it did not take is taken here."""
    logits = u @ _f32(router)
    top, idx = jax.lax.top_k(logits, top_k + 1)
    chosen = idx[:, :top_k]
    first, count = held
    here = (idx[:, top_k - 1:] >= first) & (idx[:, top_k - 1:] < first + count)
    tie = ((top[:, top_k - 1] - top[:, top_k] < ROUTING_TIE) & here.any(-1),
           chosen.at[:, top_k - 1].set(idx[:, top_k]))
    if other is not None:
        chosen = jnp.where(other[0][:, None], other[1], chosen)
    picked = jnp.sum(jax.nn.one_hot(chosen, logits.shape[-1]), axis=1) > 0
    return jax.nn.softmax(jnp.where(picked, logits, -jnp.inf), axis=-1), \
        logits, tie


def _routed(u, p, act, *, top_k, held, other):
    """The held experts' part of the routed sum over u (S, d): the router
    reads u as it is (float32 at every level, as the configuration keeps
    it), the experts what `act` makes of it."""
    g, logits, tie = _gates(u, p["router"], top_k=top_k, held=held,
                            other=other)
    u = act(u)
    first, count = held
    if p["w13"].shape[0] != count:
        raise ValueError(f"the weights hold {p['w13'].shape[0]} experts a "
                         f"layer, the configuration {count}")

    def one(total, e):                  # every row through held expert e
        a, b = jnp.split(act(u @ _f32(p["w13"][e])), 2, axis=-1)
        y = act(act(jax.nn.silu(a) * b) @ _f32(p["w2"][e]))
        return total + jax.lax.dynamic_slice_in_dim(
            g, first + e, 1, axis=1) * y, None

    total, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(count))
    return act(total), logits, tie


@functools.partial(jax.jit, static_argnames=(
    "what", "n_heads", "n_kv_heads", "mamba_heads", "d_state", "eps",
    "scale", "residual", "top_k", "held", "rounded"))
def layer(x, p, *, what, n_heads, n_kv_heads, mamba_heads, d_state, eps,
          scale, residual, top_k, held, rounded=0, other=None):
    """One layer of kind `what` over one sequence x (S, d) -> (x, the
    router's logits (S, E), its tie (`_gates`)).  `other`: the tie of the
    sound pass, whose other set is taken where it was near one."""
    with jax.default_matmul_precision(PRECISION):
        stored = lambda a: dense._round(a, rounded, 1)  # noqa: E731
        res = lambda a: dense._round(a, rounded, 2)  # noqa: E731
        act = lambda a: dense._round(a, rounded, 3)  # noqa: E731
        h = act(dense._rms_norm(x, p["input_norm"]["scale"], eps))
        if what == "mamba":
            out = dense._mamba(h, p["mamba"], act, stored, heads=mamba_heads,
                               d_state=d_state, eps=eps)
        else:
            out = dense._attention(h, p["attn"], act, stored,
                                   n_heads=n_heads, n_kv_heads=n_kv_heads,
                                   scale=scale)
        x = res(x + residual * out)
        u = dense._rms_norm(x, p["post_norm"]["scale"], eps)
        routed, logits, tie = _routed(u, p["experts"], act, top_k=top_k,
                                      held=held, other=other)
        shared = dense._mlp(act(u), p["mlp"], act)
        return res(x + residual * (routed + shared)), logits, tie


def held_experts(sizes: dict) -> tuple:
    """(first, count) of the experts the configuration holds."""
    first, count = sizes.get("experts_held") or (0, sizes["num_local_experts"])
    return int(first), int(count)


def _streams(params: dict, sizes: dict, tokens, rounded: int,
             scores: list | None = None, chosen: list | None = None) -> tuple:
    """(final hidden states (S, d) of the pass `rounded`, those of the
    sound pass beside it where `rounded` is the routing pass, else None).
    The routing pass runs BESIDE the sound one, layer by layer, and takes
    the other set where the SOUND pass is near a tie.  `chosen`: a layer's
    sets (S, top_k) as ANOTHER program took them over these tokens, taken
    here at every position (a test holds a served program's logits to the
    reference under the program's own sets, and its sets to this pass's
    margins: `scores`)."""
    p = params["params"]
    x = _f32(p["embed"]["embedding"][jnp.asarray(tokens)]) \
        * float(sizes["embedding_multiplier"])
    args = dict(n_heads=sizes["num_attention_heads"],
                n_kv_heads=sizes["num_key_value_heads"],
                mamba_heads=sizes["mamba_n_heads"],
                d_state=sizes["mamba_d_state"],
                eps=float(sizes["rms_norm_eps"]),
                scale=float(sizes["attention_multiplier"]),
                residual=float(sizes["residual_multiplier"]),
                top_k=sizes["num_experts_per_tok"],
                held=held_experts(sizes))
    routing = rounded == ROUTING_PASS
    sound = x if routing else None
    for i, what in enumerate(sizes["layer_types"]):
        tie = None if chosen is None else (
            jnp.ones(len(tokens), bool), jnp.asarray(chosen[i]))
        if routing:
            sound, _, tie = layer(sound, p[f"layers_{i}"], what=what, **args)
        x, logits, _ = layer(x, p[f"layers_{i}"], what=what, other=tie,
                             rounded=0 if routing else rounded, **args)
        if scores is not None:
            scores.append(logits)
    return x, sound


def hidden_states(params: dict, sizes: dict, tokens, rounded: int = 0,
                  scores: list | None = None) -> jax.Array:
    """Final hidden states (S, d) of one sequence of token ids; each
    layer's router logits are appended to `scores` where given."""
    return _streams(params, sizes, tokens, rounded, scores)[0]


def _head(params: dict, sizes: dict, x, rows, rounded: int):
    if not sizes["tie_word_embeddings"]:
        raise ValueError("the family ties its head to the embedding")
    p = params["params"]
    if rows is not None:
        x = x[jnp.asarray(rows)]
    table = p["embed"]["embedding"]
    step = -(-table.shape[0] // dense.VOCAB_SLICES)
    return jnp.concatenate(
        [dense.head(x, p["norm"], table[i: i + step],
                    eps=float(sizes["rms_norm_eps"]),
                    divide=float(sizes["logits_scaling"]), rounded=rounded)
         for i in range(0, table.shape[0], step)], axis=-1)


def rounded_logits(params: dict, sizes: dict, tokens, rows=None,
                   rounded: int = 0, scores: list | None = None,
                   chosen: list | None = None) -> jax.Array:
    """A level's own logits (the routing pass's: with the other sets
    taken; with `chosen`: under those sets, `_streams`), for tests and
    tools; the harness reads `logits`."""
    x, _ = _streams(params, sizes, tokens, rounded, scores, chosen)
    return _head(params, sizes, x, rows,
                 0 if rounded == ROUTING_PASS else rounded)


def logits(params: dict, sizes: dict, tokens, rows=None,
           rounded: int = 0) -> jax.Array:
    """Float32 logits of one sequence, at `rows` (all positions if None)."""
    x, sound = _streams(params, sizes, tokens, rounded)
    if sound is None:
        return _head(params, sizes, x, rows, rounded)
    return standing_of_the_other(_head(params, sizes, sound, rows, 0),
                                 _head(params, sizes, x, rows, 0))


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
