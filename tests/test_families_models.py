"""What every family's model owes its plain reference, ONE test a property
and a case a family (`tests/tiny_families.py` has the models; a family's
own tests are in its own file): tiny widths, float32, seeded weights.

Tolerances, rows and decode lengths are the family's
(`tests/tiny_families.py`, with what each was measured against); the
planted faults that show what they catch are in `test_models_sambay.py` and
`test_models_granite_hybrid.py`.
"""

import dataclasses

import numpy as np
import pytest

from tests.tiny_families import FAMILIES


@pytest.fixture(params=["sambay", "granite_hybrid", "granite_moe_hybrid"])
def recurrent(request):
    fam = FAMILIES[request.param]
    return fam, fam.model(), fam.params


def test_whole_forward_matches_the_reference(recurrent):
    import jax.numpy as jnp

    fam, model, params = recurrent
    tokens = fam.tokens(1, (2, 37))    # 37: no multiple of a chunk of 8
    got = np.asarray(model.apply(params, jnp.asarray(tokens)))
    for b in range(2):
        want = fam.reference(params, tokens[b])
        fam.check_reference(want)
        np.testing.assert_allclose(got[b], want, atol=fam.TOL, rtol=0)


def test_rows_of_one_padded_bucket_each_get_their_own_last_state(recurrent):
    """Right-padding is harmless to causal attention and wrong for a
    recurrence: each row's scan state, conv window and rings must be those
    at ITS last token, as if it had been prefilled alone."""
    import jax

    def fixed(state):   # what of a prefill's state is fixed per row
        return jax.tree_util.tree_leaves({k: state[k] for k in fam.FIXED})

    fam, model, params = recurrent
    rows = [fam.tokens(seed, n) for seed, n in fam.ROWS]
    logits, both = fam.prefill(model, params, rows, fam.BUCKET)
    for r, row in enumerate(rows):
        alone_logits, alone = fam.prefill(model, params, [row], len(row))
        fam.check_row(logits[r], alone_logits[0])
        padded, single = fixed(both), fixed(alone)
        assert len(padded) == len(single) > 0
        for two, one in zip(padded, single):
            np.testing.assert_allclose(two[r], one[0], atol=fam.STATE_TOL)
    fam.check_prefill(both)


def test_prefill_then_paged_decode_matches_the_reference(recurrent):
    """24 decode steps through rings, pages of 4 and the recurrent state,
    against the reference's whole pass over prompt + generated."""
    fam, model, params = recurrent
    seed, prompt_lens, steps = fam.DECODE
    assert fam.decode_against_reference(
        model, params, fam.tokens(seed, (2, 60)), prompt_lens,
        steps) < fam.TOL


def test_the_served_type_decodes_near_the_reference(recurrent):
    """bfloat16 weights, the engine's own prefill and decode: with two-term
    products the logits stay within 0.02 (SambaY; the plain bfloat16 whole
    forward is within 0.05 on the same tokens) and 0.06 (Granite: logits of
    deviation 0.91; measured 0.019; its routed member 0.1, measured 0.060,
    under the experts the program itself chose, each held to the reference's
    margin: `GraniteMoeHybrid.decode_against_reference`) of the float32
    reference's over 24 steps, at the widest position.  Not a strict bound at these tiny widths: it catches a path that
    rounds where it should not, or a type that does not fit the state."""
    import jax
    import jax.numpy as jnp

    fam = recurrent[0]
    cfg = dataclasses.replace(fam.cfg, dtype=jnp.bfloat16)
    params = fam.make(cfg)
    assert all(x.dtype in (jnp.bfloat16, jnp.float32)
               for x in jax.tree_util.tree_leaves(params))
    seed, prompt_lens, steps = fam.DECODE
    assert fam.decode_against_reference(
        fam.model(cfg), params, fam.tokens(seed, (2, 60)), prompt_lens,
        steps) < fam.SERVED_TOL


@pytest.mark.parametrize("name", ["lfm2_moe", "mla_moe"])
def test_the_tiny_configuration_is_the_familys(name):
    """The model file's tiny configuration is what the benchmark's family
    module makes of the tiny sizes the reference reads."""
    import importlib

    FAMILIES[name].check_tiny_configuration(
        importlib.import_module(f"benchmarks.families.{name}"))
