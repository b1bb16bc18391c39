"""LFM2-MoE's decoder (`models/lfm2_moe.py`) at tiny widths, the system
against the benchmark's plain reference on seeded random weights, compared
on LOGITS: d 64, 7 layers (a conv with the dense feed-forward, then twice
attention, conv, conv with 8 routed experts, two a token), 4 query and 2
KV heads of 16, float32.

Tolerances.  Both sides compute in float32 (conftest sets "highest" matmul
precision), in different orders: the system with KV heads paired into
heads of 32 and zero-padded queries, pages, conv windows carried from a
padded prefill into single steps, and the (row, expert) pairs sorted by
expert into one grouped product a matrix (the Pallas kernel, interpreted);
the reference with heads of 16, whole rows, and every expert over every
row, weighed by its gate.  The logits here have deviation 0.9 and lie
within +-4 (the benchmark's initialiser scaled to a width of 64, `make`);
float32 reordering moves them by up to 2e-6 (measured over every case
below).  TOL = 3e-5 leaves fifteen times that; a near-tie of the router
that the two sides break differently would read 0.05 and up, and none
occurs at this seed.  Every planted fault reads over FAULT = 1e-3, thirty
times TOL: the subtlest, gates taken from the biased scores, 0.023 in the
second batch (0.27 in the first); no head norms 0.056; the others 0.26 to
2.8.  In bfloat16 (the served type: weights and K, V rounded, activations
in two terms) the system lies within TOL_BF16 of the reference on the same
weights at EVERY position, and a program whose products take ONE bfloat16
term does not at nine positions in ten.

The model, its sizes and `make` are `tests/tiny_families.py`'s; through the
engine the family is a case of `tests/test_families_served.py`, and its
tiny configuration one of `tests/test_families_models.py`.
"""

import numpy as np
import pytest

from tests.tiny_families import lfm2_moe as family

TOL = 3e-5
FAULT = 1e-3
TOL_BF16 = 0.002
SIZES = family.SIZES
PAGE, TABLE, BUCKET = 4, 16, 32
# Two batches through the same four slots: rows of very different lengths
# in one padded bucket, and every slot used twice.
LENGTHS = ((5, 19, 12, 30), (27, 3, 22, 9))
STEPS = 10


@pytest.fixture(scope="module")
def tiny():
    return family.cfg, family.params


def _sequences(seed, lengths, extra=STEPS):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, size=n + extra).tolist() for n in lengths]


def _reference(params, seq, rows=None, sizes=SIZES):
    return family.reference(params, seq, rows, sizes)


class Served:
    """The serving family's own functions, as the engine calls them: a
    padded prefill of four rows into slots and pages, then single steps
    through pages and conv windows, each fed the sequence's next token."""

    def __init__(self, cfg, params):
        import jax

        from ray_tpu.serve.llm_families import family_of

        self.params = params
        self.fam = fam = family_of(cfg, TABLE * PAGE)
        self.state = fam.init_state(4, 4 * TABLE + 1, PAGE)
        self.tables = 1 + np.arange(4 * TABLE, dtype=np.int32).reshape(
            4, TABLE)
        self.prefill = jax.jit(fam.prefill)
        self.write = jax.jit(fam.write_prompt)
        self.decode = jax.jit(fam.decode)

    def logits(self, seqs, lengths, steps=STEPS):
        """-> for each sequence, the logits at its positions lengths[i] - 1
        ... lengths[i] - 1 + steps, and what the programs counted."""
        import jax.numpy as jnp

        tokens = np.zeros((4, BUCKET), np.int32)
        for r, (seq, n) in enumerate(zip(seqs, lengths)):
            tokens[r, :n] = seq[:n]
        lens = np.asarray(lengths, np.int32)
        first, fresh, counted = self.prefill(
            self.params, jnp.asarray(tokens), jnp.asarray(lens - 1))
        self.state = self.write(
            self.state, fresh, jnp.arange(4),
            jnp.asarray(self.tables[:, : BUCKET // PAGE]))
        out, counts = [np.asarray(first)], [np.asarray(counted)]
        for j in range(steps):
            at = jnp.asarray(lens + j)
            token = jnp.asarray([seq[n + j] for seq, n in
                                 zip(seqs, lengths)], jnp.int32)
            lg, self.state, counted = self.decode(
                self.params, token, at, self.state,
                jnp.asarray(self.tables), at, jnp.ones(4, bool))
            out.append(np.asarray(lg))
            counts.append(np.asarray(counted))
        return np.stack(out, axis=1), counts        # (4, steps + 1, V)


def _differences(params, cfg, sizes=SIZES):
    """At every compared position, the widest |logit difference| between
    the served path and the reference: one array for each batch of
    LENGTHS (the second reuses the slots)."""
    served = Served(cfg, params)
    out = []
    for b, lengths in enumerate(LENGTHS):
        seqs = _sequences(b, lengths)
        got, _ = served.logits(seqs, lengths)
        out.append(np.concatenate([
            np.abs(got[r] - _reference(
                params, seq[: n + STEPS],
                list(range(n - 1, n + STEPS)), sizes)).max(-1)
            for r, (seq, n) in enumerate(zip(seqs, lengths))]))
    return out


def _widest(params, cfg):
    return [d.max() for d in _differences(params, cfg)]


def test_full_forward_is_the_references(tiny):
    import jax.numpy as jnp

    from ray_tpu.models.lfm2_moe import Lfm2MoeModel

    cfg, params = tiny
    seqs = _sequences(7, (40, 40), extra=0)
    got = np.asarray(Lfm2MoeModel(cfg).apply(params, jnp.asarray(seqs)))
    for row, seq in zip(got, seqs):
        want = _reference(params, seq)
        assert np.abs(row - want).max() < TOL
        assert 0.6 < want.std() < 1.2       # the published widths' scale


def test_prefill_then_decode_through_pages_and_windows(tiny):
    """Right-padded rows of unequal length in one bucket, then ten single
    steps through pages and conv windows, against the reference's full
    pass; then four more rows into the SAME slots: a reused slot starts
    from what its own prefill computed."""
    cfg, params = tiny
    first, reused = _widest(params, cfg)
    assert first < TOL and reused < TOL


def test_what_the_programs_count(tiny):
    """Prefill counts the rows' real tokens (not the bucket's padding), a
    step its live rows: two pairs a token in each of six routed layers."""
    import jax.numpy as jnp

    cfg, params = tiny
    served = Served(cfg, params)
    lengths = LENGTHS[0]
    _, counts = served.logits(_sequences(0, lengths), lengths, steps=2)
    assert list(served.fam.prefill_counters) == [
        ("expert_rows_max", "max"), ("expert_rows", "sum")]
    assert counts[0][1] == sum(lengths) * 2 * 6
    assert counts[0][0] <= sum(lengths)
    touched, slots, rows_max = counts[1]
    assert slots == 6 * 8 and 6 * 2 <= touched <= 6 * 8
    assert 1 <= rows_max <= 4
    # a slot held still is given to no expert
    at = jnp.asarray(np.asarray(lengths, np.int32) + 2)
    live = jnp.asarray([True, False, False, False])
    _, _, (touched, slots, rows_max) = served.decode(
        params, jnp.ones(4, jnp.int32), at, served.state,
        jnp.asarray(served.tables), at, live)
    assert (int(touched), int(slots), int(rows_max)) == (12, 48, 1)


def test_no_token_is_dropped_at_a_skewed_routing():
    """The expert layer alone against the expert-by-expert sum with every
    row counted, at a routing in which one expert takes most rows and
    several take none."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.lfm2_moe import expert_ffn

    T, d, f, E, k = 300, 64, 32, 8, 2
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    u = jax.random.normal(keys[0], (T, d))
    w13 = jax.random.normal(keys[1], (E, d, 2 * f)) * 0.2
    w2 = jax.random.normal(keys[2], (E, f, d)) * 0.2
    # expert 3 for nine rows in ten, then one of experts 0, 5 and 6;
    # experts 1, 2, 4 and 7 take nothing
    first = jnp.where(jax.random.uniform(keys[3], (T,)) < 0.9, 3, 5)
    second = jnp.where(first == 3, jnp.asarray([0, 5, 6])[
        jax.random.randint(keys[4], (T,), 0, 3)], 6)
    idx = jnp.stack([first, second], axis=1)
    gates = jax.nn.softmax(jax.random.normal(keys[4], (T, k)), axis=-1)
    valid = jnp.arange(T) % 7 != 0
    out, counts = jax.jit(expert_ffn)(u, idx, gates, w13, w2, valid)
    want = np.zeros((T, d), np.float32)
    for e in range(E):
        a, b = np.split(np.asarray(u) @ np.asarray(w13[e]), 2, axis=-1)
        y = (a / (1 + np.exp(-a)) * b) @ np.asarray(w2[e])
        g = np.where(np.asarray(idx) == e, np.asarray(gates), 0).sum(-1)
        want += g[:, None] * y
    want *= np.asarray(valid)[:, None]
    assert np.abs(np.asarray(out) - want).max() < 1e-5
    sizes = np.bincount(np.asarray(idx)[np.asarray(valid)].reshape(-1),
                        minlength=E)
    assert sizes[3] > 0.85 * valid.sum() and (sizes == 0).sum() == 4
    assert list(np.asarray(counts)) == [4, E, sizes.max(),
                                        2 * int(valid.sum())]


def _fault_names():
    from benchmarks.tools.lfm2_moe_faults import FAULTS

    return list(FAULTS)


@pytest.mark.parametrize("fault", _fault_names())
def test_a_planted_fault_fails_the_comparison(tiny, fault):
    """Each of ISSUE 42's eleven faults, planted in the program: the
    served path's logits leave the reference's by more than FAULT (a
    reused slot's stale window in the SECOND batch only: the first finds
    zeros there)."""
    from benchmarks.tools.lfm2_moe_faults import planted

    cfg, params = tiny
    with planted(fault):
        first, reused = _widest(params, cfg)
    assert reused > FAULT
    if fault.startswith("viii"):
        assert first < TOL
    else:
        assert first > FAULT


def test_bf16_in_two_terms_holds_and_in_one_term_does_not(tiny):
    """The served type: bfloat16 weights and K, V, float32 conv windows,
    activations in two terms (the grouped products make them in the kernel,
    from `lfm2_moe._two_terms`), against
    the reference on the same weights: the widest difference over 168
    positions reads 5.5e-4 (median 2.1e-4: K and V rounded to 2^-9).  With
    every product's activation rounded to ONE bfloat16 term, as a plain
    bf16 program has it, the median reads 0.0126 and the first decile
    0.0087: TOL_BF16 = 0.002 lies between, four times and a quarter."""
    import dataclasses
    from unittest import mock

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import lfm2_moe

    cfg, params = tiny
    cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    keep = ("router", "expert_bias", "scale")
    served = jax.tree_util.tree_map_with_path(
        lambda path, x: x if path[-1].key in keep
        else x.astype(jnp.bfloat16), params)
    sizes = dict(SIZES, torch_dtype="bfloat16")
    two = np.concatenate(_differences(served, cfg, sizes))
    assert two.max() < TOL_BF16

    def one_term(a, axis):
        a = a.astype(jnp.bfloat16)
        return jnp.concatenate([a, jnp.zeros_like(a)], axis=axis)

    with mock.patch.object(lfm2_moe, "_two_terms", one_term), \
            mock.patch("ray_tpu.models.sambay._two_terms", one_term):
        one = np.concatenate(_differences(served, cfg, sizes))
    assert np.quantile(one, 0.1) > TOL_BF16


def test_the_family_sizes_state_and_prefill_from_shapes():
    """At the published sizes of the cut the benchmark serves: two pools
    of 4 paired heads of 128 (4,096 bytes of K and V a token), seven conv
    windows of (2, 2048) float32 a sequence."""
    import dataclasses

    import jax

    from ray_tpu.models.lfm2_moe import LFM2_24B_A2B, count_params
    from ray_tpu.serve.llm_families import family_of

    assert count_params(LFM2_24B_A2B)["total"] == 23_843_661_440
    cut = dataclasses.replace(
        LFM2_24B_A2B, layer_types=LFM2_24B_A2B.layer_types[1:10],
        n_dense_layers=1)
    assert count_params(cut)["total"] == 5_177_950_976
    fam = family_of(cut, 4544)
    assert fam.state_bytes_per_slot == 7 * 2 * 2048 * 4
    assert not fam.rewinds and not fam.portable_kv
    assert [fam.prefill_width(b, 16) for b in (128, 1024, 4096)] == [8, 8, 2]
    state = jax.eval_shape(lambda: fam.init_state(2, 5, 64))
    assert [tuple(x.shape) for x in state["pools"][0]] == [(5, 4, 64, 128)] * 2
    assert len(state["pools"]) == 2 and len(state["conv"]) == 7
    per_token = sum(x.size // 5 // 64 * x.dtype.itemsize
                    for x in jax.tree_util.tree_leaves(state["pools"]))
    assert per_token == 4096
