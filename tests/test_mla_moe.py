"""The latent-attention decoder with routed and shared experts
(`models/mla_moe.py`) at tiny widths, the system against the benchmark's
plain reference on seeded random weights, compared on LOGITS: d 64, 3
layers (a dense one, then two with 8 routed experts, three a token, beside
2 shared), 4 heads of 16 + 8 over a latent of 32, float32.

Tolerances.  Both sides compute in float32 (conftest sets "highest" matmul
precision), in different orders: the system decompresses a prompt's keys
and values and runs one prefill over a padded bucket, then ABSORBS the
up-projections and attends over latent pages through the Pallas kernel
(interpreted), with the rotated values laid out evens first and the (row,
expert) pairs sorted into one grouped product a matrix; the reference
decompresses at every position, rotates pairs in place, and runs every
expert over every row.  The logits lie within +-3; float32 reordering
moves them by up to 1.2e-6 (measured over every case below).  TOL = 3e-5
leaves twenty times that; a near-tie of the router that the two sides
break differently would read 0.01 and up, and none occurs at this seed.
Every planted fault reads over FAULT = 1e-3, thirty times TOL (the
subtlest, the selection bias used as a gate, 0.011).  In bfloat16 (the
served type: weights and latents rounded, activations in two terms) the
system lies within TOL_BF16 of the reference's pass that rounds what the
cache holds, on the same weights at every position.

The model, its sizes and `make` are `tests/tiny_families.py`'s; through the
engine the family is a case of `tests/test_families_served.py`, and its
tiny configuration one of `tests/test_families_models.py`.
"""

import os
import sys

import numpy as np
import pytest

from tests.tiny_families import mla_moe as family

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 3e-5
FAULT = 1e-3
TOL_BF16 = 0.01
SIZES = family.SIZES
PAGE, TABLE, BUCKET = 4, 16, 32
# Two batches through the same four slots: rows of very different lengths
# in one padded bucket (one ends ON a page boundary, one a token past
# one), and every slot used twice.
LENGTHS = ((5, 19, 12, 30), (27, 3, 22, 9))
STEPS = 10
# (`tests/benchmarks/test_mla_moe_cell.py` takes the weights from here)
make = family.make


@pytest.fixture(scope="module")
def tiny():
    return family.cfg, family.params


def _sequences(seed, lengths, extra=STEPS):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, size=n + extra).tolist() for n in lengths]


def _reference(params, seq, rows=None, sizes=SIZES, **how):
    return family.reference(params, seq, rows, sizes, **how)


class Served:
    """The serving family's own functions, as the engine calls them: a
    padded prefill of four rows into latent pages, then single steps
    through the pages, each fed the sequence's next token."""

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
                jnp.asarray(self.tables), at, None)
            out.append(np.asarray(lg))
            counts.append(np.asarray(counted))
        return np.stack(out, axis=1), counts        # (4, steps + 1, V)


def _differences(params, cfg, sizes=SIZES, **how):
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
                list(range(n - 1, n + STEPS)), sizes, **how)).max(-1)
            for r, (seq, n) in enumerate(zip(seqs, lengths))]))
    return out


def _widest(params, cfg):
    return [d.max() for d in _differences(params, cfg)]


def test_full_forward_is_the_references(tiny):
    """The whole forward (decompressed, evens-first rotation) against the
    reference (pairs rotated in place, every expert over every row)."""
    cfg, params = tiny
    from ray_tpu.models.mla_moe import MlaMoeModel

    seq = _sequences(7, (48,), extra=0)[0]
    got = np.asarray(MlaMoeModel(cfg).apply(params, np.asarray([seq])))[0]
    want = _reference(params, seq)
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < TOL


def test_prefill_then_decode_through_latent_pages(tiny):
    """A padded prefill of rows of unequal length into latent pages, then
    ten absorbed steps through the kernel, slots reused by a second
    batch: the reference's logits at every position."""
    cfg, params = tiny
    first, reused = _widest(params, cfg)
    assert first < TOL and reused < TOL


def test_absorbed_decode_is_the_decompressed_attention(tiny):
    """One layer's attention on the SAME latents both ways: the prompt
    over itself decompressed (k_nope and v a head), and its last token
    absorbed over pages that hold the earlier rows."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.mla_moe import LatentAttention

    cfg, _ = tiny
    attn = LatentAttention(cfg)
    S = 23                                  # (five whole pages and three)
    u = jax.random.normal(jax.random.PRNGKey(3), (1, S, cfg.d_model))
    positions = jnp.arange(S)[None]
    def both(module):
        q_nope, q_rope, rows = module.project(u, positions)
        whole = module.o_proj(
            module.attend(*module.decompressed(q_nope, q_rope, rows)),
            precise=True)
        pages = jnp.pad(rows[0, : S - 1], ((0, 1 + TABLE * PAGE - S), (0, 0)))
        pool = jnp.concatenate([jnp.zeros((1, PAGE, cfg.latent_row)),
                                pages.reshape(-1, PAGE, cfg.latent_row)])
        table = (1 + jnp.arange(TABLE))[None]
        last, pool = module.over_pages(
            q_nope[:, -1], q_rope[:, -1], rows[:, -1], pool, table,
            jnp.asarray([S]))
        return whole[:, -1], last, pool, rows

    variables = jax.tree_util.tree_map(
        lambda w: w * 8.0, nn.init(both, attn)(jax.random.PRNGKey(4)))
    whole, last, pool, rows = nn.apply(both, attn)(variables)
    size = float(jnp.abs(whole).max())
    assert size > 0.05
    assert float(jnp.abs(whole - last).max()) < 2e-6 * size
    # ... and the step's row is where position S - 1 lies
    at = 1 + (S - 1) // PAGE
    assert (np.asarray(pool[at, (S - 1) % PAGE]) ==
            np.asarray(rows[0, -1])).all()


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_latent_kernel_is_plain_attention_over_gathered_pages(dtype):
    """`paged_latent_attention_batch` (interpret mode) against numpy over
    each sequence's gathered pages: lengths that end inside a page, ON a
    page boundary and a token past one, an empty slot, a full table; the
    pool comes back with the step's rows written in place, bit for bit,
    and every other page as it was."""
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import paged_latent_attention_batch

    page, npages, H, W, dv = 16, 9, 4, 128, 64
    lengths = np.asarray([0, 1, 16, 17, 100, 144, 33], np.int32)
    B = len(lengths)
    rng = np.random.default_rng(0)
    pool = rng.standard_normal((B * npages + 1, page, W)).astype(np.float32)
    free = list(1 + rng.permutation(B * npages))
    tables = np.zeros((B, npages), np.int32)
    for b in range(B):
        for p in range(-(-int(lengths[b]) // page)):
            tables[b, p] = free.pop()
    q = rng.standard_normal((B, H, W)).astype(np.float32) * 0.3
    new = rng.standard_normal((B, W)).astype(np.float32)
    pool_j = jnp.asarray(pool, dtype)
    new_j = jnp.asarray(new, dtype)
    scale = 0.17
    out, got = paged_latent_attention_batch(
        jnp.asarray(q), pool_j, jnp.asarray(tables), jnp.asarray(lengths),
        new_j, d_value=dv, sm_scale=scale)
    rows = np.flatnonzero(lengths > 0)
    at = lengths[rows] - 1
    want = pool_j.at[tables[rows, at // page], at % page].set(new_j[rows])
    assert (np.asarray(got.astype(jnp.float32))
            == np.asarray(want.astype(jnp.float32))).all()
    want = np.asarray(want.astype(jnp.float32))
    for b in range(B):
        L = int(lengths[b])
        if L == 0:
            assert (np.asarray(out[b]) == 0).all()
            continue
        kb = np.concatenate([want[tables[b, p]]
                             for p in range(-(-L // page))])[:L]
        s = q[b] @ kb.T * scale
        p_ = np.exp(s - s.max(-1, keepdims=True))
        p_ /= p_.sum(-1, keepdims=True)
        # (bfloat16 rows: q and the weights enter as two terms, 2^-16)
        assert np.abs(np.asarray(out[b]) - p_ @ kb[:, :dv]).max() < \
            (1e-4 if dtype == "bfloat16" else 1e-5)


def test_the_bias_chooses_and_does_not_weigh(tiny):
    """A routed layer's feed-forward alone: with a bias that lifts the
    three WEAKEST experts over all others, they are the ones computed, at
    gates made of their own unbiased scores (2.446 x s / sum s); and the
    shared experts are added once, whatever the routing."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.mla_moe import Layer

    cfg, params = tiny
    p = params["params"]["layers_1"]
    e = p["experts"]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 1, cfg.d_model))
    u = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.norm_eps)
    s = jax.nn.sigmoid(u[0] @ e["router"])
    weakest = np.asarray(jnp.argsort(s, axis=-1)[0, :3])
    lift = jnp.zeros(cfg.n_experts).at[weakest].set(10.0)
    p = dict(p, experts=dict(e, expert_bias=lift))
    out, counts = Layer(cfg, True).apply(
        {"params": p}, x, None, method=Layer.feed_forward)

    def gated(w13, w2):
        a, b = jnp.split(u[0] @ w13, 2, axis=-1)
        return (jax.nn.silu(a) * b) @ w2

    g = s[0, weakest]
    g = cfg.routed_scaling * g / g.sum()
    routed = sum(g[j] * gated(e["w13"][i], e["w2"][i])
                 for j, i in enumerate(weakest))
    shared = gated(p["shared"]["w13"]["kernel"], p["shared"]["w2"]["kernel"])
    assert float(jnp.abs(shared).max()) > 1e-3
    assert float(jnp.abs(out[0] - (x[0] + routed + shared)).max()) < 1e-5
    assert counts.tolist() == [3, 8, 1, 3]


def test_route_takes_each_familys_constant():
    import jax.numpy as jnp

    from ray_tpu.models.lfm2_moe import route

    logits = jnp.asarray([[-40.0, -41.0, -60.0]])
    bias = jnp.zeros(3)
    _, coarse = route(logits, bias, 2)          # sum of scores << 1e-6
    _, fine = route(logits, bias, 2, eps=1e-20)
    assert float(coarse.sum()) < 1e-6
    assert float(fine.sum()) == pytest.approx(1.0, abs=5e-3)


# (k, n) of a call's matrices as the two routed cells make it -> its column
# tile; the float32 rows of the call (one a (token, expert) pair: 6 pairs a
# token in `kimivl-serve-pages-closed`, 4 in `lfm2moe-serve-agents-closed`)
# and its 64 groups, for `test_the_sweep_runs_the_cells_own_shapes`.
_KIMI_W13, _KIMI_W2 = (2048, 2816), (1408, 2048)
_LFM2_W13, _LFM2_W2 = (2048, 3072), (1536, 2048)
_CALLS = {
    "kimi decode w13": ((64 * 6, 64) + _KIMI_W13, 2816),
    "kimi decode w2": ((64 * 6, 64) + _KIMI_W2, 2048),
    "kimi prompt 512 w13": ((512 * 6, 64) + _KIMI_W13, 2816),
    "kimi prompt 2048 w13": ((2048 * 6, 64) + _KIMI_W13, 2816),
    "kimi prompt 8192 w2": ((8192 * 6, 64) + _KIMI_W2, 2048),
    "lfm2 decode w13": ((16 * 4, 64) + _LFM2_W13, 3072),
    "lfm2 decode w2": ((16 * 4, 64) + _LFM2_W2, 2048),
    "lfm2 prompt 128 w13": ((128 * 4, 64) + _LFM2_W13, 3072),
    "lfm2 prompt 512 w2": ((512 * 4, 64) + _LFM2_W2, 2048),
    "lfm2 prompt 1024 w13": ((1024 * 4, 64) + _LFM2_W13, 3072),
    "lfm2 prompt 4096 w2": ((4096 * 4, 64) + _LFM2_W2, 2048),
    "a tiny model": ((8, 8, 64, 64), 64),
    "a matrix too large for VMEM twice": ((384, 64, 4096, 2816), 1408),
}


@pytest.mark.parametrize("call", _CALLS)
def test_grouped_product_tiles_hold_a_groups_whole_matrix(call):
    """One tiling for a decode step and a prompt (until PR 47 the row tile
    followed the rows a group can hold, 128 or 256 DOUBLED rows, and the
    columns went in 512s, 256s or two halves): 64 float32 rows, the
    contraction whole, and the columns whole, so that a group's matrix is
    brought in once a group; a matrix that does not fit VMEM twice goes in
    the widest multiple of 128 that divides its columns and does.  The
    call fits the VMEM it asks for."""
    from ray_tpu.ops import grouped_matmul as gm

    (_, _, k, n), col_tile = _CALLS[call]
    assert gm._tiles(k, n) == (64, k, col_tile)
    assert n % col_tile == 0 and (col_tile % 128 == 0 or col_tile == n)
    assert gm._vmem_bytes(64, k, n, col_tile, 2) <= gm._VMEM_LIMIT


def _doubled_rows(x, w, sizes, tiles):
    """The grouped product as it was until PR 47: `megablox.gmm` over the
    two terms of each row as two adjacent rows of the same group, the
    group sizes doubled, the two halves of the result added."""
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from ray_tpu.models.sambay import _halves, _two_terms

    M, k = x.shape
    rows = _two_terms(x[:, None], 1).reshape(2 * M, k)
    out = gmm(jnp.pad(rows, ((0, -2 * M % tiles[0]), (0, 0))), w,
              2 * sizes, jnp.float32, tiles, interpret=True)[: 2 * M]
    return _halves(out.reshape(M, 2, -1), 1)[:, 0]


# groups of 0, 1, 12 and 300 rows, and 7 rows past the last group
_SIZES = np.asarray([0, 1, 12, 300], np.int32)
_ROWS, _K, _N = 320, 64, 1536
# the row tile `_tiles` chooses and the one a prompt took over doubled rows;
# the columns whole (what `_tiles` chooses where the matrix fits), in halves
# and in narrower multiples of 128 that divide them
_TILINGS = [(tm, tn) for tm in (64, 128) for tn in (1536, 768, 512, 384, 128)]


def _rows_and_matrices(dtype):
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    return (jnp.asarray(rng.standard_normal((_ROWS, _K)), jnp.float32),
            jnp.asarray(rng.standard_normal((4, _K, _N)) * 0.1, dtype),
            jnp.asarray(_SIZES))


@pytest.fixture(scope="module")
def doubled_rows():
    import jax.numpy as jnp

    x, w, sizes = _rows_and_matrices(jnp.bfloat16)
    return np.asarray(_doubled_rows(x, w, sizes, (256, _K, 512)))


@pytest.mark.parametrize("row_tile, col_tile", _TILINGS)
def test_the_kernel_makes_the_two_terms_of_the_doubled_rows(
        monkeypatch, doubled_rows, row_tile, col_tile):
    """Float32 rows in, the two bfloat16 terms made inside the kernel:
    bit for bit what `megablox.gmm` gives over the doubled rows with the
    two halves added, for groups of 0, 1, 12 and 300 rows, at every tile
    `_tiles` can choose; the rows past the last group come back as
    anything."""
    import jax.numpy as jnp

    from ray_tpu.models.sambay import _two_terms
    from ray_tpu.ops import grouped_matmul as gm

    assert gm._tiles(_K, _N) == _TILINGS[0][:1] + (_K, _TILINGS[0][1])
    held = int(_SIZES.sum())
    x, w, sizes = _rows_and_matrices(jnp.bfloat16)
    monkeypatch.setattr(gm, "_tiles", lambda *a: (row_tile, _K, col_tile))
    got = np.asarray(gm.grouped_matmul(x, w, sizes, _two_terms))
    assert got.shape == (_ROWS, _N) and got.dtype == np.float32
    assert (got[:held].view(np.uint32)
            == doubled_rows[:held].view(np.uint32)).all()
    # and both terms are in it: the product of the float32 rows to 2^-16
    group = np.repeat(np.arange(4), _SIZES)
    plain = np.einsum("rk,rkn->rn", np.asarray(x)[:held],
                      np.asarray(w, np.float32)[group])
    assert np.abs(got[:held] - plain).max() < 1e-4


def test_float32_matrices_take_the_rows_in_one_term():
    """The tests' tiny models: float32 matrices, the rows as they are, no
    split (`two_terms` is not called): `megablox.gmm` over the same rows."""
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from ray_tpu.ops import grouped_matmul as gm

    def never(a, axis):
        raise AssertionError("float32 matrices: one term")

    held = int(_SIZES.sum())
    x, w, sizes = _rows_and_matrices(jnp.float32)
    got = np.asarray(gm.grouped_matmul(x, w, sizes, never))[:held]
    want = np.asarray(gmm(jnp.pad(x, ((0, 64), (0, 0))), w, sizes,
                          jnp.float32, (128, _K, 512), interpret=True))[:held]
    assert (got.view(np.uint32) == want.view(np.uint32)).all()


@pytest.mark.parametrize("rows_as", ["float32", "bfloat16"])
def test_a_rows_product_does_not_depend_on_its_tile(monkeypatch, rows_as):
    """With the contraction whole, a row's result is the same bits at
    every tile: groups of 0, 1, 12 and 300 rows and rows past the last
    group, at a prompt's row tile, at smaller ones and at half the column
    tile.  Float32 rows (two terms made inside) and, as until PR 47,
    bfloat16 ones (their second term is zero)."""
    import jax.numpy as jnp

    from ray_tpu.models.sambay import _two_terms
    from ray_tpu.ops import grouped_matmul as gm

    held, n = int(_SIZES.sum()), 256
    x, w, sizes = _rows_and_matrices(jnp.bfloat16)
    x, w = x.astype(rows_as), w[:, :, :n]

    def at(row_tile, cols=n):
        monkeypatch.setattr(gm, "_tiles", lambda *a: (row_tile, _K, cols))
        return np.asarray(gm.grouped_matmul(x, w, sizes, _two_terms))[:held]

    old = at(128)
    group = np.repeat(np.arange(4), _SIZES)
    plain = np.einsum("rk,rkn->rn", np.asarray(x, np.float32)[:held],
                      np.asarray(w, np.float32)[group])
    assert np.abs(old - plain).max() < 1e-4
    for tiles in ((64,), (32,), (16,), (64, n // 2)):
        assert (at(*tiles).view(np.uint32) == old.view(np.uint32)).all()


@pytest.mark.parametrize("family, decode_call, touched", [
    ("mla_moe", "kimi decode w13", 63), ("lfm2_moe", "lfm2 decode w13", 40)])
def test_the_sweep_runs_the_cells_own_shapes(family, decode_call, touched):
    """`scripts/tpu_kernel_sweep.py --gmm` takes its shapes from the cells'
    configuration files: the decode call is the one `_CALLS` names, a
    prompt's rows are one a (token, expert) pair, and the two products'
    least times add up to the benchmark's `grouped_product_cost`."""
    import importlib

    sys.path.insert(0, os.path.join(_REPO, "scripts"))
    try:
        sweep = importlib.import_module("tpu_kernel_sweep")
    finally:
        sys.path.remove(os.path.join(_REPO, "scripts"))
    conf, costs, cases, products = sweep._gmm_cases(family)
    costs = importlib.import_module("benchmarks.layer_metrics." + costs)
    (rows, groups, k, n), _ = _CALLS[decode_call]
    label, sizes, _ = cases[0]
    assert label.startswith("decode") and products[0] == ("w13", k, n)
    assert (int(sizes.sum()), len(sizes), int((sizes > 0).sum())) == \
        (rows, groups, touched)
    for label, sizes, tokens in cases[1:]:
        assert sizes.sum() == tokens * conf["num_experts_per_tok"]
    # beside it, `megablox.gmm` over the doubled rows at PR 46's tiles
    assert sweep._doubled_rows_tiles(2 * rows, groups, k, n) == \
        (128, k, 1408 if family == "mla_moe" else 512)
    pairs = rows
    parts = [sweep._gmm_cost(pairs, touched, a, b) for _, a, b in products]
    assert np.allclose(np.sum(parts, axis=0),
                       costs.grouped_product_cost(conf, pairs, touched))
    assert all(tk == k and n % tn == 0
               for _, tk, tn in sweep._gmm_tilings(k, n))
    # a chip's share of a layer: 36 groups held of 72 routed over, so half
    # of a decode step's 480 pairs and about half of a prompt's lie here
    share_conf, share_costs, cases, products = sweep._gmm_cases(
        "granite_moe_hybrid")
    share_costs = importlib.import_module(
        "benchmarks.layer_metrics." + share_costs)
    assert np.allclose(     # (the file as it lies is enough for the costs)
        np.sum([sweep._gmm_cost(240, 36, a, b) for _, a, b in products],
               axis=0), share_costs.grouped_product_cost(share_conf, 240, 36))
    assert products == (("w13", 4096, 1536), ("w2", 768, 4096))
    assert [(label, len(sizes), int(sizes.sum())) for label, sizes, _
            in cases[:1]] == [("decode 48", 36, 240)]
    for label, sizes, tokens in cases[1:]:
        assert len(sizes) == 36 and 0.45 < sizes.sum() / (tokens * 10) < 0.55


def _fault_names():
    from benchmarks.tools.mla_moe_faults import FAULTS

    return list(FAULTS)


@pytest.mark.parametrize("fault", _fault_names())
def test_a_planted_fault_fails_the_comparison(tiny, fault):
    """Each of ISSUE 45's faults, planted in the program: the served
    path's logits leave the reference's by more than FAULT."""
    from benchmarks.tools.mla_moe_faults import planted

    cfg, params = tiny
    with planted(fault):
        first, reused = _widest(params, cfg)
    assert first > FAULT and reused > FAULT


def test_bf16_in_two_terms_holds_and_in_one_term_does_not(tiny):
    """The served type: bfloat16 weights and latents, activations in two
    terms, against the reference's pass that rounds what the cache holds
    (level 1) on the same weights: the widest difference over 176
    positions reads 0.0044 (median 0.0011: at 3-40 tokens of context
    attention is most of this tiny stream, and a latent that the two sides
    round to different neighbours moves a logit by that; against the
    unrounded pass the median is ten times as much).  With the experts'
    rows in ONE bfloat16 term (the tolerance's control) the median reads
    0.0051: TOL_BF16 = 0.01 holds the first, and the second's median lies
    over three times the first's."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmarks.tools.mla_moe_faults import planted

    cfg, params = tiny
    cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    keep = ("router", "expert_bias", "scale")
    served = jax.tree_util.tree_map_with_path(
        lambda path, x: x if path[-1].key in keep
        else x.astype(jnp.bfloat16), params)
    sizes = dict(SIZES, torch_dtype="bfloat16")
    two = np.concatenate(_differences(served, cfg, sizes, rounded=1))
    assert two.max() < TOL_BF16
    with planted("experts_in_one_bf16_term"):
        one = np.concatenate(_differences(served, cfg, sizes, rounded=1))
    assert np.median(one) > 3 * np.median(two)


def test_the_reference_is_the_installed_deepseek_v3(tiny):
    """The reference against `transformers`' DeepseekV3ForCausalLM at the
    tiny preset, seeded weights copied across (nothing is downloaded):
    the same logits to float32 reordering."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    try:
        from transformers import DeepseekV3Config, DeepseekV3ForCausalLM
    except ImportError:
        pytest.skip(f"transformers {transformers.__version__} has no "
                    "deepseek_v3")
    cfg, params = tiny
    p = params["params"]
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "n_shared_experts",
            "n_routed_experts", "routed_scaling_factor", "kv_lora_rank",
            "q_lora_rank", "qk_rope_head_dim", "v_head_dim",
            "qk_nope_head_dim", "n_group", "topk_group",
            "num_experts_per_tok", "first_k_dense_replace", "norm_topk_prob",
            "hidden_act", "max_position_embeddings", "rms_norm_eps",
            "rope_theta", "rope_scaling", "attention_bias",
            "tie_word_embeddings")
    hf_cfg = DeepseekV3Config(**{k: SIZES[k] for k in keys},
                              attn_implementation="eager")
    assert hf_cfg.rope_interleave is True   # the model type's default
    model = DeepseekV3ForCausalLM(hf_cfg).eval()
    t = lambda a: torch.tensor(np.asarray(a, np.float32))  # noqa: E731
    lin = lambda a: t(a).T.contiguous()  # noqa: E731
    state = {"model.embed_tokens.weight": t(p["embed"]["embedding"]),
             "model.norm.weight": t(p["norm"]["scale"]),
             "lm_head.weight": lin(p["lm_head"]["kernel"])}
    f = cfg.d_expert
    for i in range(cfg.n_layers):
        lp, pre = p[f"layers_{i}"], f"model.layers.{i}."
        a = lp["attn"]
        state.update({
            pre + "input_layernorm.weight": t(lp["attn_norm"]["scale"]),
            pre + "post_attention_layernorm.weight":
                t(lp["ffn_norm"]["scale"]),
            pre + "self_attn.q_proj.weight": lin(a["q_proj"]["kernel"]),
            pre + "self_attn.kv_a_proj_with_mqa.weight":
                lin(a["kv_a_proj"]["kernel"]),
            pre + "self_attn.kv_a_layernorm.weight":
                t(a["kv_norm"]["scale"]),
            pre + "self_attn.kv_b_proj.weight":
                lin(np.asarray(a["kv_b"]).reshape(cfg.kv_rank, -1)),
            pre + "self_attn.o_proj.weight": lin(a["o_proj"]["kernel"])})

        def gated(prefix, w13, w2):
            w13 = np.asarray(w13)
            half = w13.shape[1] // 2
            state[prefix + "gate_proj.weight"] = lin(w13[:, :half])
            state[prefix + "up_proj.weight"] = lin(w13[:, half:])
            state[prefix + "down_proj.weight"] = lin(w2)

        if "mlp" in lp:
            gated(pre + "mlp.", lp["mlp"]["w13"]["kernel"],
                  lp["mlp"]["w2"]["kernel"])
            continue
        e = lp["experts"]
        state[pre + "mlp.gate.weight"] = lin(e["router"])
        state[pre + "mlp.gate.e_score_correction_bias"] = t(e["expert_bias"])
        gated(pre + "mlp.shared_experts.", lp["shared"]["w13"]["kernel"],
              lp["shared"]["w2"]["kernel"])
        for j in range(cfg.n_experts):
            gated(pre + f"mlp.experts.{j}.", e["w13"][j], e["w2"][j])
        assert np.asarray(e["w13"]).shape[-1] == 2 * f
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not unexpected
    assert all("rotary" in m or "inv_freq" in m for m in missing), missing
    seq = _sequences(11, (40,), extra=0)[0]
    with torch.no_grad():
        theirs = model(torch.tensor([seq])).logits[0].numpy()
    ours = _reference(params, seq)
    assert np.abs(theirs).max() > 1.0
    assert np.abs(theirs - ours).max() < TOL


def test_the_family_sizes_state_and_prefill_from_shapes():
    """At the published sizes of the cut the benchmark serves: one pool a
    layer of rows of 640 (576 cached values: 1,152 bytes a token a layer,
    8,064 over seven), nothing fixed per slot."""
    import dataclasses

    import jax

    from ray_tpu.models.mla_moe import KIMI_VL_A3B, count_params
    from ray_tpu.serve.llm_families import family_of

    whole = count_params(KIMI_VL_A3B)
    assert 15.9e9 < whole["total"] < 16.0e9         # published "16B"
    cut = dataclasses.replace(KIMI_VL_A3B, n_layers=7)
    assert count_params(cut)["total"] == 4_263_151_488
    fam = family_of(cut, 9280)
    state = jax.eval_shape(lambda: fam.init_state(64, 11, 64))
    assert [x.shape for x in state] == [(11, 64, 640)] * 7
    assert cut.latent_dim * 2 == 1152 and cut.latent_dim * 2 * 7 == 8064
    assert fam.state_bytes_per_slot == 0 and fam.rewinds
    assert [fam.prefill_width(b, 64) for b in (512, 1024, 2048, 4096, 8192)] \
        == [8, 8, 4, 2, 1]
