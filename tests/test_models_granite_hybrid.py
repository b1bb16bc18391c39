"""Granite-4.0-H's decoder (`models/granite_hybrid.py`) at tiny widths, the
system against the benchmark's plain reference on seeded random weights:
d 64, 8 layers (mamba, mamba, attention, mamba) x 2, Mamba-2 with 4 heads
of 32 and state 16 in chunks of 8, attention with 4 query and 2 KV heads
of 16, float32.

Tolerances.  Both sides compute in float32 (conftest sets "highest"
matmul precision), in different orders: the system with KV heads paired
into heads of 32 and zero-padded queries, pages, and the recurrence in its
chunked matrix form; the reference with heads of 16, whole rows and the
recurrence one token at a time.  The logits here lie within +-3.6 (the
benchmark's initialiser scaled to a width of 64, `make`: deviation 0.91);
float32 reordering moves them by up to 5e-6.  TOL = 3e-5 leaves six
times that.  Every planted fault below reads over FAULT = 1e-3, thirty
times TOL: the state held in bfloat16 for 24 steps reads 5.1e-3 (the
subtlest: a state of 32 x 16 a head here, 64 x 128 published; 2.1e-4 under
Mamba-2's published initialiser, whose heads forget in tens of steps and
whose state is a thirtieth of a layer's output), bfloat16 K, V and conv
windows 0.032, a reused slot's state 1.0, the others from 2.6 up.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

TOL = 3e-5
FAULT = 1e-3
SIZES = dict(
    hidden_size=64, intermediate_size=128, shared_intermediate_size=128,
    num_hidden_layers=8,
    layer_types=["mamba", "mamba", "attention", "mamba"] * 2,
    num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
    mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16, mamba_d_conv=4,
    mamba_n_groups=1, mamba_expand=2, mamba_chunk_size=8,
    attention_multiplier=0.015625, embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=8, rms_norm_eps=1e-5,
    position_embedding_type="nope", rope_theta=10000,
    max_position_embeddings=256, tie_word_embeddings=True,
    torch_dtype="float32")
PAGE, TABLE, BUCKET = 4, 16, 32


def make(cfg, seed=0):
    """The benchmark's initialiser with the matrices' deviations scaled
    from the published width to this one (sqrt(2048 / 64)), so that
    activations, step sizes and attention scores have the scale they have
    at the published widths: the state then carries as much of a layer's
    output as the skip term does, and a fault in it shows.  The embedding
    keeps its deviation and the final norm's scale takes the factor
    instead: the logits' deviation is the published widths' (0.91),
    and the token just read, whose embedding enters the stream times 12
    and is also its row of the head, is not what the layers are drowned
    by (with the embedding scaled too, greedy decoding here repeats its
    input at 19 positions in 20, whatever the state holds)."""
    import jax

    from benchmarks.families.granite_hybrid import WEIGHTS
    from ray_tpu.models.granite_hybrid import init_params

    wider = (2048 / cfg.d_model) ** 0.5
    scaled = {k: WEIGHTS[k] * wider
              for k in ("in_std", "qkv_std", "out_std", "final_norm")}
    return init_params(cfg, jax.random.PRNGKey(seed),
                       **dict(WEIGHTS, **scaled))


@pytest.fixture(scope="module")
def tiny():
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.models.granite_hybrid import (TINY_GRANITE,
                                               GraniteHybridModel)

    cfg = TINY_GRANITE
    assert list(cfg.layer_types) == SIZES["layer_types"]
    return cfg, GraniteHybridModel(cfg), make(cfg)


def _reference(params, tokens, rounded=0, **sizes):
    from benchmarks.reference import granite_hybrid as ref

    return np.asarray(ref.logits(params, dict(SIZES, **sizes), list(tokens),
                                 rounded=rounded))


def _tokens(seed, shape):
    return np.random.default_rng(seed).integers(1, 256, size=shape)


def _prefill(model, params, rows, bucket, last=None):
    """Right-padded rows through `prefill` -> logits, state."""
    import jax.numpy as jnp

    from ray_tpu.models.granite_hybrid import GraniteHybridModel

    padded = np.zeros((len(rows), bucket), np.int32)
    for r, row in enumerate(rows):
        padded[r, : len(row)] = row
    if last is None:
        last = [len(row) - 1 for row in rows]
    return model.apply(params, jnp.asarray(padded),
                       jnp.asarray(last, jnp.int32),
                       method=GraniteHybridModel.prefill)


def _paged_state(fresh, batch):
    """The prefill's state with its K and V cut into the pages of pools:
    row b owns pages 1 + b * TABLE ..., page 0 is nobody's."""
    import jax.numpy as jnp

    table = jnp.asarray(
        1 + np.arange(batch * TABLE).reshape(batch, TABLE), jnp.int32)

    def pool(a):
        B, H, S, D = a.shape
        pages = a.reshape(B, H, S // PAGE, PAGE, D).transpose(0, 2, 1, 3, 4)
        out = jnp.zeros((1 + batch * TABLE, H, PAGE, D), a.dtype)
        return out.at[table[:, : S // PAGE].reshape(-1)].set(
            pages.reshape(-1, H, PAGE, D))

    return {"ssm": fresh["ssm"],
            "pools": [(pool(k), pool(v)) for k, v in fresh["kv"]]}, table


def test_whole_forward_matches_the_reference(tiny):
    import jax.numpy as jnp

    cfg, model, params = tiny
    tokens = _tokens(1, (2, 37))       # 37: no multiple of the chunk of 8
    got = np.asarray(model.apply(params, jnp.asarray(tokens)))
    for b in range(2):
        want = _reference(params, tokens[b])
        assert 0.3 < want.std() < 1.0
        np.testing.assert_allclose(got[b], want, atol=TOL, rtol=0)


def test_the_benchmarks_weights_give_the_heads_a_long_memory(tiny):
    """`init_params(step_size=, decay=)` draws each head's dt and |A| from
    the ranges handed in (`families/granite_hybrid.WEIGHTS`: a head forgets
    in 1 / (dt |A|) = 160 to 100,000 steps, so the state outweighs the
    skip term and a fault in it shows); without them the module's own draw
    stands, Mamba-2's published initialiser (1 to 1,000 steps)."""
    import jax

    from benchmarks.families.granite_hybrid import WEIGHTS
    from ray_tpu.models.granite_hybrid import init_params

    def heads(params):
        for name, layer in params["params"].items():
            if "mamba" in layer:
                m = layer["mamba"]
                yield (np.asarray(jax.nn.softplus(m["dt_bias"])),
                       np.exp(np.asarray(m["a_log"])))

    cfg, _, params = tiny
    (dt_lo, dt_hi), (a_lo, a_hi) = WEIGHTS["step_size"], WEIGHTS["decay"]
    drawn = list(heads(params))
    assert len(drawn) == 6
    for dt, a in drawn:
        assert ((dt_lo * 0.999 <= dt) & (dt <= dt_hi * 1.001)).all()
        assert ((a_lo * 0.999 <= a) & (a <= a_hi * 1.001)).all()
        assert ((160 <= 1 / (dt * a)) & (1 / (dt * a) <= 102_400)).all()
    assert len({float(dt[0]) for dt, _ in drawn}) == 6      # a draw a layer
    for dt, a in heads(init_params(cfg, jax.random.PRNGKey(0))):
        assert ((1e-3 * 0.999 <= dt) & (dt <= 0.1 * 1.001)).all()
        assert ((1 <= a) & (a <= 16)).all()


def test_the_chunked_matrix_form_equals_the_recurrence():
    """`ssd_scan` across chunk boundaries (50 positions in chunks of 16,
    the last one short), with a state handed in and a row that ended
    early, against the recurrence one token at a time."""
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.models.granite_hybrid import ssd_scan

    B, S, H, P, N = 2, 50, 3, 8, 4
    keys = jax.random.split(jax.random.PRNGKey(6), 6)
    dt = jax.nn.softplus(jax.random.normal(keys[0], (B, S, H)))
    dt = dt.at[1, 40:].set(0.0)           # a row that ended at 39
    x = jax.random.normal(keys[1], (B, S, H, P))
    b_sel = jax.random.normal(keys[2], (B, S, N))
    c_sel = jax.random.normal(keys[3], (B, S, N))
    a = -jnp.exp(jax.random.normal(keys[4], (H,)) * 0.5)
    s0 = jax.random.normal(keys[5], (B, H, P, N))
    y, s = ssd_scan(x, dt, a, b_sel, c_sel, s0, chunk=16)
    want_y = np.zeros((B, S, H, P), np.float32)
    state = np.asarray(s0)
    held = None
    for t in range(S):
        d = np.asarray(dt[:, t])                                # (B, H)
        state = np.exp(d * np.asarray(a))[..., None, None] * state \
            + (d[..., None] * np.asarray(x[:, t]))[..., None] \
            * np.asarray(b_sel[:, t])[:, None, None, :]
        want_y[:, t] = np.einsum("bhpn,bn->bhp", state,
                                 np.asarray(c_sel[:, t]))
        if t == 39:
            held = state[1].copy()
    np.testing.assert_allclose(np.asarray(y), want_y, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(s), state, atol=2e-5, rtol=2e-5)
    # dt = 0 left the second row's state where its last token put it
    np.testing.assert_allclose(np.asarray(s)[1], held, atol=2e-5, rtol=2e-5)
    one = ssd_scan(x, dt, a, b_sel, c_sel, s0, chunk=64)   # one chunk
    np.testing.assert_allclose(np.asarray(y), np.asarray(one[0]),
                               atol=2e-5, rtol=2e-5)


def test_rows_of_one_padded_bucket_each_get_their_own_last_state(tiny):
    """Right-padding is harmless to causal attention and wrong for a
    recurrence: each row's state and conv window must be those at ITS
    last token, as if it had been prefilled alone."""
    cfg, model, params = tiny
    rows = [_tokens(3, 27), _tokens(4, 11), _tokens(5, 2)]
    logits, both = _prefill(model, params, rows, BUCKET)
    for r, row in enumerate(rows):
        alone_logits, alone = _prefill(model, params, [row], len(row))
        np.testing.assert_allclose(logits[r], alone_logits[0], atol=TOL)
        for (conv2, s2), (conv1, s1) in zip(both["ssm"], alone["ssm"]):
            np.testing.assert_allclose(conv2[r], conv1[0], atol=1e-5)
            np.testing.assert_allclose(s2[r], s1[0], atol=1e-5)
    assert len(both["ssm"]) == 6 and len(both["kv"]) == 2
    # K and V of two KV heads of 16 lie side by side in one head of 32
    assert both["kv"][0][0].shape == (3, 1, BUCKET, 32)


def _decode_against_reference(model, params, seqs, prompt_lens, steps,
                              rounded=0, fault=None, **sizes):
    """Prefill the prompts in one bucket, then `steps` teacher-forced
    paged decode steps; the widest gap to the reference's full pass.
    `fault(what, state)` may spoil the state on its way."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.granite_hybrid import GraniteHybridModel

    fault = fault or (lambda what, state: state)
    B = len(seqs)
    logits, fresh = _prefill(
        model, params, [s[:n] for s, n in zip(seqs, prompt_lens)], BUCKET)
    state, table = _paged_state(fault("prefilled", fresh), B)
    want = [_reference(params, s, rounded, **sizes) for s in seqs]
    worst = max(np.abs(np.asarray(logits[b]) - want[b][n - 1]).max()
                for b, n in enumerate(prompt_lens))
    decode = jax.jit(lambda p, t, s, ln: model.apply(
        p, t, ln, s, table, ln, method=GraniteHybridModel.decode))
    length = jnp.asarray(prompt_lens, jnp.int32)
    for k in range(steps):
        token = jnp.asarray([s[n + k] for s, n in zip(seqs, prompt_lens)])
        logits, state = decode(params, token, state, length)
        state = fault("stepped", state)
        for b, n in enumerate(prompt_lens):
            worst = max(worst, np.abs(np.asarray(logits[b])
                                      - want[b][n + k]).max())
        length = length + 1
    return worst


SEQS, PROMPTS, STEPS = (7, (2, 60)), [21, 13], 24


def test_prefill_then_paged_decode_matches_the_reference(tiny):
    """24 decode steps through pages of 4 and the recurrent state, against
    the reference's whole pass over prompt + generated."""
    cfg, model, params = tiny
    assert _decode_against_reference(model, params, _tokens(*SEQS), PROMPTS,
                                     STEPS) < TOL


# ---- planted faults: each must read far over TOL ---------------------------


def _state_in_bf16(what, state):
    import jax.numpy as jnp

    if what != "stepped":
        return state
    return dict(state, ssm=[
        (conv, s.astype(jnp.bfloat16).astype(jnp.float32))
        for conv, s in state["ssm"]])


def _conv_window_not_carried(what, state):
    import jax.numpy as jnp

    if what != "prefilled":
        return state
    return dict(state, ssm=[(jnp.zeros_like(conv), s)
                            for conv, s in state["ssm"]])


def _last_streams_state_kept(what, state):
    """A reused slot whose admission did not replace S: the rows start
    from each other's state."""
    if what != "prefilled":
        return state
    return dict(state, ssm=[(conv, s[::-1]) for conv, s in state["ssm"]])


@pytest.mark.parametrize("fault", [_state_in_bf16, _conv_window_not_carried,
                                   _last_streams_state_kept],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_fault_in_the_state_is_seen(tiny, fault):
    cfg, model, params = tiny
    assert _decode_against_reference(model, params, _tokens(*SEQS), PROMPTS,
                                     STEPS, fault=fault) > FAULT


def test_padding_that_leaks_into_a_rows_state_is_seen(tiny):
    """The state taken at the bucket's end instead of the row's: what
    `prefill` would hand on if it did not hold the recurrence still past
    `last_idx`."""
    cfg, model, params = tiny
    seqs = _tokens(*SEQS)

    def leak(what, state):
        if what != "prefilled":
            return state
        _, at_the_end = _prefill(
            model, params, [s[:n] for s, n in zip(seqs, PROMPTS)], BUCKET,
            last=[BUCKET - 1] * len(PROMPTS))
        return dict(state, ssm=at_the_end["ssm"])

    assert _decode_against_reference(model, params, seqs, PROMPTS, STEPS,
                                     fault=leak) > FAULT


@pytest.mark.parametrize("wrong", [dict(residual_multiplier=1.0),
                                   dict(rope_theta=1e4)],
                         ids=["residual_multiplier_left_out",
                              "rotary_applied"])
def test_a_model_wired_otherwise_is_seen(tiny, wrong):
    from ray_tpu.models.granite_hybrid import GraniteHybridModel

    cfg, _, params = tiny
    model = GraniteHybridModel(dataclasses.replace(cfg, **wrong))
    assert _decode_against_reference(model, params, _tokens(*SEQS), PROMPTS,
                                     STEPS) > FAULT


def test_the_tolerance_would_refuse_bf16_kv_and_conv_windows(tiny):
    """The same run against the reference with K, V and the conv's inputs
    rounded to bfloat16 (its first level of rounding) misses TOL: the
    tolerance sees a cache held in a lower precision."""
    cfg, model, params = tiny
    assert _decode_against_reference(model, params, _tokens(*SEQS), PROMPTS,
                                     STEPS, rounded=1) > FAULT


def test_the_parameter_count_is_the_published_one():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.granite_hybrid import (GRANITE_4_H_MICRO,
                                               GraniteHybridModel,
                                               count_params)

    counts = count_params(GRANITE_4_H_MICRO)
    shapes = jax.eval_shape(
        lambda: GraniteHybridModel(GRANITE_4_H_MICRO).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    assert counts["total"] == 3_191_396_096 == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert (counts["mamba"], counts["attention"]) == (76_182_976,
                                                      60_821_504)
    assert GRANITE_4_H_MICRO.layers_of("attention") == [5, 15, 25, 35]


def test_the_served_type_decodes_near_the_reference(tiny):
    """bfloat16 weights, the engine's own prefill and decode: with
    two-term products the logits stay within 0.06 of the float32
    reference's (logits of deviation 0.91; measured 0.019) over 24 steps.
    Not a strict bound at these tiny widths: it catches a path that rounds
    where it should not, or a type that does not fit the state."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.granite_hybrid import GraniteHybridModel

    cfg = dataclasses.replace(tiny[0], dtype=jnp.bfloat16)
    params = make(cfg)
    assert all(x.dtype in (jnp.bfloat16, jnp.float32)
               for x in jax.tree_util.tree_leaves(params))
    assert _decode_against_reference(GraniteHybridModel(cfg), params,
                                     _tokens(*SEQS), PROMPTS, STEPS) < 0.06
