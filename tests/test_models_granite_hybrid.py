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

The model and `make` are `tests/tiny_families.py`'s; what this family owes
its reference as every recurrent family does (the whole forward, rows of
one padded bucket, prefill then paged decode, the served type) is held, a
case a family, by `tests/test_families_models.py`.
"""

import dataclasses

import numpy as np
import pytest

from tests.tiny_families import granite_hybrid as family

TOL = family.TOL       # (3e-5)
FAULT = 1e-3
BUCKET = family.BUCKET


@pytest.fixture(scope="module")
def tiny():
    cfg = family.cfg
    assert list(cfg.layer_types) == family.SIZES["layer_types"]
    return cfg, family.model(), family.params


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


# (as `tests/test_families_models.py` decodes them)
SEQS, PROMPTS, STEPS = (7, (2, 60)), [21, 13], 24


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
    assert family.decode_against_reference(
        model, params, family.tokens(*SEQS), PROMPTS, STEPS,
        fault=fault) > FAULT


def test_padding_that_leaks_into_a_rows_state_is_seen(tiny):
    """The state taken at the bucket's end instead of the row's: what
    `prefill` would hand on if it did not hold the recurrence still past
    `last_idx`."""
    cfg, model, params = tiny
    seqs = family.tokens(*SEQS)

    def leak(what, state):
        if what != "prefilled":
            return state
        _, at_the_end = family.prefill(
            model, params, [s[:n] for s, n in zip(seqs, PROMPTS)], BUCKET,
            last=[BUCKET - 1] * len(PROMPTS))
        return dict(state, ssm=at_the_end["ssm"])

    assert family.decode_against_reference(
        model, params, seqs, PROMPTS, STEPS, fault=leak) > FAULT


@pytest.mark.parametrize("wrong", [dict(residual_multiplier=1.0),
                                   dict(rope_theta=1e4)],
                         ids=["residual_multiplier_left_out",
                              "rotary_applied"])
def test_a_model_wired_otherwise_is_seen(tiny, wrong):
    from ray_tpu.models.granite_hybrid import GraniteHybridModel

    cfg, _, params = tiny
    model = GraniteHybridModel(dataclasses.replace(cfg, **wrong))
    assert family.decode_against_reference(
        model, params, family.tokens(*SEQS), PROMPTS, STEPS) > FAULT


def test_the_tolerance_would_refuse_bf16_kv_and_conv_windows(tiny):
    """The same run against the reference with K, V and the conv's inputs
    rounded to bfloat16 (its first level of rounding) misses TOL: the
    tolerance sees a cache held in a lower precision."""
    cfg, model, params = tiny
    assert family.decode_against_reference(
        model, params, family.tokens(*SEQS), PROMPTS, STEPS,
        rounded=1) > FAULT


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
