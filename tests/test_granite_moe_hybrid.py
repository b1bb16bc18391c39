"""Granite-4.0-H's decoder WITH its routed experts (`models/granite_hybrid.py`
at `n_experts` > 0, `models/lfm2_moe.expert_ffn` told which experts it
holds) at tiny widths, float32, seeded weights, the benchmark's plain
reference (`benchmarks/reference/granite_moe_hybrid.py`) as the judge: d 512,
8 layers (mamba, mamba, attention, mamba) x 2, a shared feed-forward of 64
beside 8 routed experts of 32, three a token, heads of 128 unpaired.

What every recurrent family owes its reference (whole forward, rows of one
padded bucket, prefill then paged decode, the served type) is held by
`tests/test_families_models.py`, and the engine's streams through slots and
pages (a reused slot among them) by `tests/test_families_served.py`, a case
a family.  Here: the SHARE.  Two chips that hold experts 0-3 and 4-7 route
over all eight and each adds its part; the parts, added, with the shared
expert counted once, are the uncut layer.

Tolerances: both sides compute in float32 in different orders (the program
sorts its pairs and sums a row's experts as the grouped product leaves
them, the reference runs each expert over all rows); logits within +-3.6
move by up to 6e-6, TOL = 4e-5 (`tests/tiny_families.py`).  Every planted
fault reads over FAULT = 1e-2, two hundred and fifty times TOL.
"""

import dataclasses

import numpy as np
import pytest

from tests.tiny_families import granite_moe_hybrid as family
from tests.tiny_families import lfm2_moe, mla_moe

TOL = family.TOL
FAULT = 1e-2


@pytest.fixture(scope="module")
def tiny():
    import jax

    jax.config.update("jax_platforms", "cpu")
    return family.cfg, family.model(), family.params


def _apply(model, params, tokens):
    import jax.numpy as jnp

    return np.asarray(model.apply(params, jnp.asarray(tokens)))


def test_the_tiny_configuration_is_the_familys(tiny):
    """The model file's tiny configuration is what the benchmark's family
    module makes of the sizes the reference reads, and `count_params`
    counts the tree (all held, and a share of four)."""
    import jax
    from benchmarks.families import granite_moe_hybrid as bench_family
    from ray_tpu.models.granite_hybrid import count_params

    cfg, _, params = tiny
    assert bench_family.program_config(
        family.SIZES, attention="reference") == cfg
    leaves = lambda p: sum(x.size for x in  # noqa: E731
                           jax.tree_util.tree_leaves(p))
    assert count_params(cfg)["total"] == leaves(params)
    half_cfg, half = family.share(4, 4)
    assert count_params(half_cfg)["total"] == leaves(half) == \
        leaves(params) - 8 * 4 * count_params(cfg)["expert"]
    assert count_params(cfg)["router"] == 512 * 8
    with pytest.raises(ValueError, match="a run of the router's 8 experts"):
        family.model(dataclasses.replace(cfg, experts_held=(6, 4))).init(
            jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))


def test_the_router_takes_the_top_logits_and_weighs_them_alone():
    """Granite's router is not `lfm2_moe.route`: plain logits, the k
    largest, a softmax over THOSE k (by hand on two rows)."""
    import jax.numpy as jnp
    from ray_tpu.models.granite_hybrid import route

    logits = jnp.asarray([[0.0, 2.0, 1.0, -1.0, 3.0],
                          [5.0, 5.0, -2.0, 0.0, 1.0]])
    idx, gates = route(logits, 3)
    assert np.asarray(idx).tolist() == [[4, 1, 2], [0, 1, 4]]
    e = np.exp([3.0, 2.0, 1.0])
    np.testing.assert_allclose(np.asarray(gates[0]), e / e.sum(), atol=1e-6)
    e = np.exp([5.0, 5.0, 1.0])
    np.testing.assert_allclose(np.asarray(gates[1]), e / e.sum(), atol=1e-6)
    # over all five logits the same experts would weigh less
    assert float(gates[0, 0]) > float(jnp.exp(3.0) / jnp.exp(logits[0]).sum())


def test_two_shares_of_a_layer_add_up_to_the_uncut_feed_forward(tiny):
    """One layer's feed-forward over 40 rows (five of them not valid): the
    routed parts that the shares 0-3 and 4-7 give, added, plus the shared
    expert ONCE, equal the uncut reference's routed + shared; each part
    alone does not; the counts split as the pairs do."""
    import jax
    import jax.numpy as jnp
    from benchmarks.reference import granite_moe_hybrid as ref
    from ray_tpu.models.granite_hybrid import MLP, route
    from ray_tpu.models.lfm2_moe import RoutedExperts

    cfg, _, params = tiny
    p = params["params"]["layers_3"]
    u = jnp.asarray(np.random.default_rng(0).normal(size=(40, 512)),
                    jnp.float32)
    valid = jnp.arange(40) < 35
    want_routed, _, _ = ref._routed(u, p["experts"], lambda a: a, top_k=3,
                                    held=(0, 8), other=None)
    shared = MLP(cfg).apply({"params": p["mlp"]}, u)
    np.testing.assert_allclose(
        np.asarray(shared), np.asarray(ref.dense._mlp(u, p["mlp"],
                                                      lambda a: a)),
        atol=2e-5)
    parts, counts = [], []
    for first in (0, 4):
        scfg, cut = family.share(first, 4)
        out, c = RoutedExperts(scfg, choose=route, held=(first, 4)).apply(
            {"params": cut["params"]["layers_3"]["experts"]}, u, valid)
        parts.append(np.asarray(out))
        counts.append(np.asarray(c))
    whole, c_whole = RoutedExperts(cfg, choose=route).apply(
        {"params": p["experts"]}, u, valid)
    want = np.where(np.asarray(valid)[:, None], np.asarray(want_routed), 0.0)
    np.testing.assert_allclose(parts[0] + parts[1], want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(whole), want, atol=2e-5)
    assert np.abs(parts[0] - want).max() > 0.1 < np.abs(parts[1] - want).max()
    # a row that is not valid gets zeros from either share
    assert not parts[0][35:].any() and not parts[1][35:].any()
    # touched and held a share, the most rows, the pairs that lay here:
    # 35 valid rows x 3 pairs, split between the two
    c_whole = np.asarray(c_whole)
    assert counts[0][1] == counts[1][1] == 4 and c_whole[1] == 8
    assert counts[0][3] + counts[1][3] == c_whole[3] == 35 * 3
    assert counts[0][0] + counts[1][0] == c_whole[0]
    assert max(counts[0][2], counts[1][2]) == c_whole[2]
    # the whole feed-forward: the shared expert counted once
    np.testing.assert_allclose(
        parts[0] + parts[1] + np.asarray(shared),
        want + np.asarray(ref.dense._mlp(u, p["mlp"], lambda a: a)),
        atol=4e-5)
    del jax


def test_a_model_whose_last_layer_is_shared_out_adds_up_to_the_uncut_logits(
        tiny):
    """Seven layers with every expert, the eighth on two chips (experts
    0-3 and 4-7): each chip's stream after it holds the mixer, the shared
    expert and ITS part of the routed sum; the two, less the stream that
    holds no routed part, through the head, are the uncut reference's
    logits."""
    import jax.numpy as jnp
    from ray_tpu.models.granite_hybrid import (GraniteHybridModel, Layer,
                                               matmul)

    cfg, model, params = tiny
    tokens = family.tokens(2, (1, 29))
    want = family.reference(params, tokens[0])
    p = params["params"]
    lead = dataclasses.replace(cfg, layer_types=cfg.layer_types[:7])
    x7 = GraniteHybridModel(lead).apply(
        {"params": {k: v for k, v in p.items() if k != "layers_7"}},
        jnp.asarray(tokens), method=lambda m, t: m._rows(t)[0])

    def last_layer(c, weights):
        return np.asarray(Layer(c, "mamba").apply(
            {"params": weights}, x7,
            method=lambda layer, x: layer.mix(
                x, lambda h: layer.mamba(h, None))[0]))

    parts = [last_layer(*(lambda c, w: (c, w["params"]["layers_7"]))(
        *family.share(first, 4))) for first in (0, 4)]
    no_routed = last_layer(
        dataclasses.replace(cfg, n_experts=0, top_k=0, d_expert=0),
        {k: v for k, v in p["layers_7"].items() if k != "experts"})
    uncut = last_layer(cfg, p["layers_7"])
    np.testing.assert_allclose(parts[0] + parts[1] - no_routed, uncut,
                               atol=2e-5)
    assert np.abs(parts[0] - uncut).max() > 0.01

    def head(x):
        f = np.asarray(x, np.float32)
        normed = f / np.sqrt((f * f).mean(-1, keepdims=True) + cfg.norm_eps) \
            * np.asarray(p["norm"]["scale"])
        return np.asarray(matmul(jnp.asarray(normed),
                                 p["embed"]["embedding"].T, True)) \
            / cfg.logits_scaling

    np.testing.assert_allclose(head(parts[0] + parts[1] - no_routed)[0],
                               want, atol=TOL)
    np.testing.assert_allclose(_apply(model, params, tokens)[0], want,
                               atol=TOL)


@pytest.mark.parametrize("first", [0, 4])
def test_a_share_matches_the_reference_that_holds_the_same_share(tiny,
                                                                 first):
    """The program that holds four of eight experts in EVERY layer, against
    the reference told the same: whole forward, then prefill and 24 paged
    decode steps.  What the absent experts would add is left out in both,
    and the partial result goes on to the next layer."""
    cfg, params = family.share(first, 4)
    model = family.model(cfg)
    held = dict(experts_held=[first, 4])
    tokens = family.tokens(3, (1, 37))
    want = family.reference(params, tokens[0], sizes=dict(family.SIZES,
                                                          **held))
    np.testing.assert_allclose(_apply(model, params, tokens)[0], want,
                               atol=TOL)
    # and it is not the uncut model's answer
    uncut = family.reference(family.params, tokens[0])
    assert np.abs(want - uncut).max() > FAULT
    seed, prompt_lens, steps = family.DECODE
    assert family.decode_against_reference(
        model, params, family.tokens(seed, (2, 60)), prompt_lens, steps,
        **held) < TOL


def test_a_decode_step_counts_its_pairs_and_those_held_here():
    """`GraniteHybridServing.decode` over a share: the five counts of
    `step_counters` in their order; a row that is not live is given to no
    expert and counted nowhere."""
    import jax.numpy as jnp
    from ray_tpu.serve.llm_families import family_of

    cfg, params = family.share(4, 4)
    fam = family_of(cfg, 64)
    assert [n for n, _ in fam.step_counters] == [
        "experts_touched", "expert_slots", "expert_rows_max",
        "expert_pairs_held", "expert_pairs"]
    assert [n for n, _ in fam.prefill_counters] == ["expert_rows_max",
                                                    "expert_rows"]
    assert not hasattr(family_of(family.cfg.__class__(), 64),
                       "step_counters")
    state = fam.init_state(3, 9, 16)
    tables = jnp.asarray(np.arange(1, 9).reshape(2, 4).tolist() + [[0] * 4],
                         jnp.int32)
    live = jnp.asarray([True, True, False])
    logits, _, counts = fam.decode(
        params, jnp.asarray([5, 9, 0]), jnp.zeros((3,), jnp.int32), state,
        tables, jnp.zeros((3,), jnp.int32), live)
    touched, slots, most, held, pairs = np.asarray(counts).tolist()
    assert logits.shape == (3, 256)
    assert slots == 8 * 4 and pairs == 2 * 3 * 8
    assert 0 < held < pairs and 0 < touched <= min(slots, held)
    assert 1 <= most <= 2
    # a prefill of two rows of 5 and 3 real tokens: its two counts
    tokens = jnp.asarray(family.tokens(1, (2, 16)))
    _, _, c = fam.prefill(params, tokens, jnp.asarray([4, 2]))
    assert c.shape == (2,) and 0 < int(c[1]) < (5 + 3) * 3 * 8
    assert fam.prefill_width(2048, 48) == 2 and fam.prefill_width(64, 48) == 8


FAULTS = ["the_share_ignored", "gates_a_softmax_over_every_logit", "top_2",
          "the_shared_expert_left_out", "attention_multiplier_doubled",
          "absent_pairs_keep_their_gate"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_comparison(tiny, fault, monkeypatch):
    """Each fault of `benchmarks/tools/granite_moe_hybrid_faults.py` that a
    whole forward can show, at tiny widths, against the reference that
    holds experts 0-3: every one reads over FAULT."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import granite_hybrid, lfm2_moe as routed

    cfg, params = family.share(0, 4)
    sizes = dict(family.SIZES, experts_held=[0, 4])
    tokens = family.tokens(4, (1, 33))
    want = family.reference(params, tokens[0], sizes=sizes)
    if fault == "the_share_ignored":        # all eight computed
        cfg, params = family.cfg, family.params
    elif fault == "gates_a_softmax_over_every_logit":
        def over_all(logits, top_k):
            _, idx = jax.lax.top_k(logits, top_k)
            return idx, jnp.take_along_axis(
                jax.nn.softmax(logits, axis=-1), idx, axis=-1)
        monkeypatch.setattr(granite_hybrid, "route", over_all)
    elif fault == "top_2":
        cfg = dataclasses.replace(cfg, top_k=2)
    elif fault == "the_shared_expert_left_out":
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: x * 0 if [k.key for k in path][-3:-1] ==
            ["mlp", "out_proj"] else x, params)
    elif fault == "attention_multiplier_doubled":
        cfg = dataclasses.replace(cfg, attention_multiplier=1 / 32)
    elif fault == "absent_pairs_keep_their_gate":
        real = routed.expert_ffn
        # the absent experts' pairs land on held expert 0 and are weighed
        monkeypatch.setattr(
            routed, "expert_ffn",
            lambda u, idx, gates, w13, w2, valid=None, first=None: real(
                u, jnp.where(idx < first + w13.shape[0], idx, first), gates,
                w13, w2, valid, first))
    got = _apply(family.model(cfg), params, tokens)[0]
    assert np.abs(got - want).max() > FAULT


def _parents_expert_ffn(u, idx, gates, w13, w2, valid=None):
    """`lfm2_moe.expert_ffn` as the parent commit has it (PR 51), word for
    word: what every expert held must stay bit-equal to."""
    import flax.linen as nn
    import jax.numpy as jnp
    from ray_tpu.models.lfm2_moe import _two_terms, expert_counts
    from ray_tpu.ops.grouped_matmul import grouped_matmul

    T, k = idx.shape
    E = w13.shape[0]
    flat = idx.T.reshape(-1)
    if valid is not None:
        flat = jnp.where(jnp.tile(valid, k), flat, E)
    order = jnp.argsort(flat)
    sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1, mode="drop")
    x = u[order % T]
    a, b = jnp.split(grouped_matmul(x, w13, sizes, _two_terms), 2, axis=-1)
    y = grouped_matmul(nn.silu(a) * b, w2, sizes, _two_terms)
    y = y[jnp.argsort(order)].reshape(k, T, -1)
    kept = gates if valid is None else jnp.where(valid[:, None], gates, 0.0)
    kept = kept.T[..., None]
    out = jnp.sum(jnp.where(kept > 0, y, 0.0) * kept, axis=0)
    return out, expert_counts(sizes)


@pytest.mark.parametrize("which", ["lfm2_moe", "mla_moe"])
@pytest.mark.parametrize("masked", [False, True], ids=["all", "some_valid"])
def test_every_expert_held_is_bit_equal_to_the_parent(which, masked):
    """The two routed families' tiny models: one routed layer's weights,
    its own router's choice over 50 rows, through `expert_ffn` as it is
    now (no share stated, and the share (0, all) stated) and as the parent
    has it: the same bits, output and counts."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.lfm2_moe import expert_ffn, route, router_logits

    fam = {"lfm2_moe": lfm2_moe, "mla_moe": mla_moe}[which]
    layers = fam.params["params"]
    p = next(v["experts"] for k, v in sorted(layers.items())
             if isinstance(v, dict) and "experts" in v)
    u = jnp.asarray(np.random.default_rng(1).normal(size=(50, 64)),
                    jnp.float32)
    idx, gates = route(router_logits(u, p["router"]), p["expert_bias"],
                       fam.cfg.top_k)
    valid = jnp.arange(50) % 7 != 3 if masked else None
    want, want_counts = jax.jit(_parents_expert_ffn)(
        u, idx, gates, p["w13"], p["w2"], valid)
    for first in (None, 0):
        got, counts = jax.jit(expert_ffn, static_argnames="first")(
            u, idx, gates, p["w13"], p["w2"], valid, first=first)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(want_counts))
