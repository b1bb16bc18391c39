"""SambaY (Phi-4-mini-flash-reasoning's architecture) at tiny widths, the
system against the benchmark's plain reference on seeded random weights:
d 64, 8 layers in the published pattern (0-3 Mamba/window, 4 Mamba, 5
full, 6-7 GMU/cross), window 8, heads of 16, float32.

Tolerances.  Both sides compute in float32 (conftest sets "highest"
matmul precision), in different orders: the system with heads of 32 and
zero-padded queries, rings, pages and a scan in chunks; the reference
with heads of 16, whole rows and one step of the scan at a time.  The
logits here lie within +-1.2; float32 reordering moves them by 1e-7 to
5e-7.  TOL = 2e-5 leaves two orders of room above that and is two orders
under what K and V held in bfloat16 cost (5e-3 here, shown by
`test_the_tolerance_would_refuse_bf16_kv`).

The model is `tests/tiny_families.py`'s; what this family owes its
reference as every recurrent family does (the whole forward, rows of one
padded bucket, prefill then paged decode, the served type) is held, a case
a family, by `tests/test_families_models.py`.
"""

import numpy as np
import pytest

from tests.tiny_families import sambay as family

TOL = family.TOL       # (2e-5)
SEQS = (5, (2, 60))     # as `tests/test_families_models.py` decodes them


@pytest.fixture(scope="module")
def tiny():
    cfg = family.cfg
    assert [cfg.kind(i) for i in range(8)] == [
        "mamba", "window", "mamba", "window", "mamba", "full", "gmu",
        "cross"]
    return cfg, family.model(), family.params


def test_prefill_that_skips_the_cross_decoder_equals_the_full_forward(tiny):
    """Prefill runs layers 0..5 over the prompt and 6-7 at the last token
    only; its logits are the whole forward's at that token."""
    import jax.numpy as jnp

    cfg, model, params = tiny
    tokens = family.tokens(2, (1, 29))
    full = np.asarray(model.apply(params, jnp.asarray(tokens)))
    logits, state = family.prefill(model, params, [tokens[0]], 32)
    np.testing.assert_allclose(np.asarray(logits[0]), full[0, -1],
                               atol=TOL, rtol=0)
    # and the cache it hands on covers the row, for one layer only
    assert state["cache"][0].shape == (1, cfg.kv_pairs, 32,
                                       2 * cfg.head_dim)
    assert len(state["rings"]) == 2 and len(state["mamba"]) == 3


def test_the_tolerance_would_refuse_bf16_kv(tiny):
    """The same run against the reference with K and V rounded to
    bfloat16 (its first level of rounding) misses TOL a hundredfold: the
    tolerance is tight enough to see a cache held in a lower precision."""
    cfg, model, params = tiny
    assert family.decode_against_reference(
        model, params, family.tokens(*SEQS), [21, 13], 24,
        rounded=1) > 50 * TOL


def test_the_chunked_scan_equals_the_sequential_one(tiny):
    """Across chunk boundaries (50 positions in chunks of 16, the last one
    short), with a state handed in, against one step at a time."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.ssm import chunked_selective_scan

    B, S, E, N = 2, 50, 12, 4
    keys = jax.random.split(jax.random.PRNGKey(6), 6)
    delta = jax.nn.softplus(jax.random.normal(keys[0], (B, S, E)))
    delta = delta.at[1, 40:].set(0.0)     # a row that ended at 39
    u = jax.random.normal(keys[1], (B, S, E))
    b_sel = jax.random.normal(keys[2], (B, S, N))
    c_sel = jax.random.normal(keys[3], (B, S, N))
    a = -jnp.exp(jax.random.normal(keys[4], (E, N)) * 0.5)
    h0 = jax.random.normal(keys[5], (B, N, E))
    y, h = chunked_selective_scan(delta, u, b_sel, c_sel, a, h0, chunk=16)
    want_y = np.zeros((B, S, E), np.float32)
    state = np.asarray(h0).swapaxes(1, 2)                      # (B, E, N)
    held = None
    for t in range(S):
        d = np.asarray(delta[:, t])
        state = np.exp(d[..., None] * np.asarray(a)) * state \
            + (d * np.asarray(u[:, t]))[..., None] \
            * np.asarray(b_sel[:, t])[:, None, :]
        want_y[:, t] = np.einsum("ben,bn->be", state,
                                 np.asarray(c_sel[:, t]))
        if t == 39:
            held = state[1].copy()
    np.testing.assert_allclose(np.asarray(y), want_y, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h).swapaxes(1, 2), state,
                               atol=1e-5, rtol=1e-5)
    # delta = 0 left the second row's state where its last token put it
    np.testing.assert_allclose(np.asarray(h)[1].T, held, atol=1e-5,
                               rtol=1e-5)
    one = chunked_selective_scan(delta, u, b_sel, c_sel, a, h0, chunk=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(one[0]),
                               atol=1e-5, rtol=1e-5)


def test_window_attention_sees_exactly_the_window(tiny):
    """Blocks of `window` queries against two blocks of keys equal the
    plain masked form, at a length that is no multiple of the window."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.sambay import masked_attention, window_attention

    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (2, 4, 21, 32))
    k = jax.random.normal(keys[1], (2, 1, 21, 32))
    v = jax.random.normal(keys[2], (2, 1, 21, 32))
    pos = jnp.arange(21)
    seen = (pos[None, :] <= pos[:, None]) & (pos[None, :] > pos[:, None] - 8)
    np.testing.assert_allclose(
        np.asarray(window_attention(q, k, v, 8, 0.25)),
        np.asarray(masked_attention(q, k, v, seen, 0.25)), atol=1e-5)


def test_the_parameter_count_is_the_published_one():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.sambay import (PHI4_MINI_FLASH, SambaYModel,
                                       count_params)

    counts = count_params(PHI4_MINI_FLASH)
    shapes = jax.eval_shape(
        lambda: SambaYModel(PHI4_MINI_FLASH).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    assert counts["total"] == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert round(counts["total"] / 1e9, 2) == 3.85     # "3.8B"
    assert [round(counts[k] / 1e6, 1) for k in
            ("mamba", "window", "gmu", "cross")] == [119.9, 98.3, 104.9,
                                                     91.8]


def test_two_term_products_do_not_round_the_activation():
    """`matmul(precise=True)` against bfloat16 weights: the activation
    enters as its rounded value plus what the rounding left, so the
    product is that of the float32 activation (to 2^-16), where the plain
    bfloat16 product is off by 2^-9 of its operands; `masked_attention`
    does the same with its queries and softmax weights."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.sambay import masked_attention, matmul

    keys = jax.random.split(jax.random.PRNGKey(8), 5)
    x = jax.random.normal(keys[0], (6, 256))
    w = jax.random.normal(keys[1], (256, 64)).astype(jnp.bfloat16)
    exact = np.asarray(x, np.float64) @ np.asarray(w.astype(jnp.float32),
                                                   np.float64)
    plain = np.abs(np.asarray(matmul(x, w), np.float64) - exact).max()
    two = np.abs(np.asarray(matmul(x, w, True), np.float64) - exact).max()
    assert two < 5e-3 * plain
    assert matmul(x, w).dtype == jnp.bfloat16
    assert matmul(x, w, True).dtype == jnp.float32
    q = jax.random.normal(keys[2], (2, 4, 1, 32))
    k = jax.random.normal(keys[3], (2, 1, 40, 32)).astype(jnp.bfloat16)
    v = jax.random.normal(keys[4], (2, 1, 40, 32)).astype(jnp.bfloat16)
    seen = jnp.ones((1, 40), bool)
    want = np.asarray(masked_attention(
        q, k.astype(jnp.float32), v.astype(jnp.float32), seen, 0.2))
    off = [np.abs(np.asarray(masked_attention(q, k, v, seen, 0.2, p))
                  - want).max() for p in (False, True)]
    assert off[1] < 0.02 * off[0]


# ---- a prompt in blocks from the host (`SambaYServing.prefill_from_host`) --

# (bucket, windows of a block, lengths of the bucket's rows); the window is 8
IN_BLOCKS = {
    "shorter-than-a-window": (64, 1, [5]),
    "one-token": (64, 1, [1]),
    "a-blocks-last-position": (64, 1, [16]),
    "a-blocks-first-position": (64, 1, [17]),
    "mid-block": (64, 1, [29]),
    "fills-its-bucket": (64, 1, [64]),
    "rows-that-end-in-other-blocks": (64, 1, [40, 9, 1]),
    "one-fills-one-ends-on-an-edge": (64, 1, [64, 32]),
    "neighbours-across-an-edge": (64, 1, [17, 16]),
    "blocks-of-two-windows": (64, 2, [40, 9, 17]),
    "blocks-of-four-windows-one-row-fills": (64, 4, [64, 33]),
    "a-bucket-of-no-whole-blocks": (27, 2, [27, 11]),
    "a-bucket-shorter-than-a-block": (16, 4, [13, 16]),
}


@pytest.fixture(scope="module")
def over_rows(tiny):
    """The oracle: the whole forward's logits, and the carried layers over
    whole rows AT ONCE from a fresh state (the stream, the memory, the state
    at each row's last token) with the full layer's K and V of every
    position; each jitted once a shape."""
    import jax
    import jax.numpy as jnp

    _, model, params = tiny

    def at_once(m, tokens, last):
        x, memory, state = m._carried(
            m.embed(tokens).astype(jnp.float32), 0, last,
            m.fresh_state(tokens.shape[0]))
        layer = m.layers[m.cfg.n_self - 1]
        return x, memory, state, layer.attn.keys_values(layer.input_norm(x))

    return (jax.jit(lambda t: model.apply(params, t)),
            jax.jit(lambda t, last: model.apply(params, t, last,
                                                method=at_once)))


@pytest.mark.parametrize("case", IN_BLOCKS)
def test_a_prompt_in_blocks_equals_the_whole_forward(tiny, over_rows, case):
    """A block of positions a program, each after the state the one before
    left, then the tail: the logits are the whole forward's at each row's
    last token (what lies right of it in the bucket is read by nothing, and
    past the longest row's block computed by nothing); every conv window,
    scan state and ring is what the layers hold at that token when they run
    over the whole row at once, whichever block the row ended in; the
    stream and the memory handed to the tail are theirs there; and K and V
    of every real position are theirs."""
    import jax
    import jax.numpy as jnp

    cfg, model, params = tiny
    forward, at_once = over_rows
    S, windows, lengths = IN_BLOCKS[case]
    serving = family.serving(cfg, windows)
    B = len(lengths)
    rows = family.tokens(sum(lengths), (B, S))
    tokens = np.where(np.arange(S)[None] < np.asarray(lengths)[:, None],
                      rows, 0).astype(np.int32)
    last = np.asarray(lengths, np.int32) - 1
    assert serving.prompt_blocks(lengths) == -(-max(lengths) // serving.block)
    assert serving.prefill_computed(S, lengths) == \
        B * serving.prompt_blocks(lengths) * serving.block
    handed = {}     # what the last block's program handed to the tail
    tail = serving._tail
    serving._tail = lambda bucket, p, state, kv, at: \
        handed.update(state) or tail(bucket, p, state, kv, at)
    try:
        logits, fresh = serving.prefill_from_host(params, tokens, last)
    finally:
        serving._tail = tail
    wx, wmemory, want, wcache = at_once(jnp.asarray(tokens),
                                        jnp.asarray(last))
    np.testing.assert_allclose(
        logits, np.asarray(forward(jnp.asarray(tokens)))[np.arange(B), last],
        atol=TOL, rtol=0)
    assert len(fresh["mamba"]) == 3 and len(fresh["rings"]) == 2
    for kind in ("mamba", "rings"):
        for got, whole in zip(jax.tree_util.tree_leaves(fresh[kind]),
                              jax.tree_util.tree_leaves(want[kind])):
            assert got.shape == whole.shape and got.dtype == whole.dtype
            np.testing.assert_allclose(got, whole, atol=1e-6, rtol=0)
    for got, whole in zip(fresh["cache"], wcache):
        assert got.shape == (B, cfg.kv_pairs, S, 2 * cfg.head_dim)
        for b, n in enumerate(lengths):
            np.testing.assert_allclose(got[b, :, :n], whole[b, :, :n],
                                       atol=1e-6, rtol=0)
    np.testing.assert_allclose(
        handed["x"], np.asarray(wx)[np.arange(B), last], atol=TOL, rtol=0)
    np.testing.assert_allclose(
        handed["memory"], np.asarray(wmemory)[np.arange(B), last], atol=TOL,
        rtol=0)


@pytest.mark.parametrize("windows", [1, 2])
def test_decode_goes_on_from_the_state_the_blocks_left(tiny, monkeypatch,
                                                       windows):
    """Sixteen teacher-forced paged decode steps (twice round the window)
    from what the blocks and the tail left, rows that ended in different
    blocks: the reference's logits at every position."""
    cfg, model, params = tiny
    blocks_of = family.serving(cfg, windows)
    monkeypatch.setattr(type(family), "serving", lambda self, cfg: blocks_of)
    assert family.decode_against_reference(
        model, params, family.tokens(11, (2, 60)), [29, 8], 16) < TOL


def _primitives(f, *args):
    """(primitive, shapes of its outputs) of every equation of `f`'s jaxpr,
    those inside its loops and calls too."""
    import jax

    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            found.append((eqn.primitive.name,
                          tuple(v.aval.shape for v in eqn.outvars)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(f)(*args).jaxpr)
    return found


@pytest.fixture(scope="module")
def programs():
    """What the whole forward, a prompt's block and its tail are made of,
    with the flash kernel configured: (the model's configuration, the three
    lists of primitives)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.llm_families import SambaYServing

    cfg = dataclasses.replace(family.cfg, attention="flash", d_ff=136)
    serving = SambaYServing(cfg, 128)
    model, B, S = serving.model, 2, 64
    tokens = jnp.zeros((B, S), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                               tokens[:, :8]))
    last = jnp.asarray([40, 9], jnp.int32)
    state, kv = jax.eval_shape(lambda: serving._fresh(B))
    return cfg, serving.block, (
        _primitives(lambda p, t: model.apply(p, t), params, tokens),
        _primitives(serving._block, params, tokens[:, :serving.block],
                    jnp.int32(8), last, state),
        _primitives(lambda *a: serving._tail(S, *a), params, state,
                    [kv] * (S // serving.block), last))


def test_a_prefill_calls_no_attention_kernel_and_feeds_forward_once_a_row(
        programs):
    """With the flash kernel configured, the whole forward calls it (the
    full layer over every position) and neither program of a prompt does;
    and a prompt's feed-forwards over (rows, positions) are those of layers
    0 .. L/2 alone, in the block's program: the full layer's and the
    cross-decoder's run in the tail, at ONE token a row."""
    cfg, block, (forward, a_block, tail) = programs
    assert any(name == "pallas_call" for name, _ in forward)
    assert not any(name == "pallas_call" for name, _ in a_block + tail)
    # (an MLP's gate and up products are the two a layer that come out d_ff
    # wide, which no other width of this model is)
    wide = lambda found, rows: sum(  # noqa: E731
        name == "dot_general" and out[0] == (2, rows, cfg.d_ff)
        for name, out in found)
    assert wide(forward, 64) == 2 * cfg.n_layers
    assert wide(a_block, block) == 2 * (cfg.n_self - 1)
    assert wide(a_block, 1) == wide(tail, block) == 0
    assert wide(tail, 1) == 2 * (cfg.n_layers - cfg.n_self + 1)


def test_no_program_of_a_prompt_loops_over_its_blocks(programs):
    """The loop over a prompt's blocks is the host's.  The block's program
    holds the loops its layers hold over ANY positions (a Mamba layer's
    scan, a window layer's map over windows: one each) and none around
    them; no conditional and no `while` anywhere; the tail holds no loop at
    all."""
    cfg, _, (_, a_block, tail) = programs
    loops = lambda found: [  # noqa: E731
        name for name, _ in found if name in ("scan", "while", "cond")]
    assert loops(a_block) == ["scan"] * (cfg.n_self - 1)
    assert loops(tail) == []
