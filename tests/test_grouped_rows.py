"""The grouped kernel moves its own rows (`ops/grouped_matmul.py`, PR 54):
a routed layer's pair rows come in by a prefetched id, `silu(a) * b` is the
first product's last step, and the second product writes each pair's row
where it belongs.  `models/lfm2_moe.expert_ffn` against the layer as it was
until then (XLA lays the sorted rows out, gates them and brings them back
to pair order AROUND a kernel over rows that lie sorted), in interpret
mode: the row copies alone give the same bits, the gate inside the kernel
the tolerance the families' tests hold a layer to.
"""

import os
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a layer's result lies within this of the layer as it was (float32 `silu`
# made by the kernel and by XLA differ in the last bit of an activation)
TOL = 1e-6
TILE = 64


def _sweep():
    """`scripts/tpu_kernel_sweep.py`: the layer as the parent commit has it
    (PR 52), XLA moving the rows, is the copy `--ffn` times beside the
    tree's own, so that what the sweep's table compares is what these
    tests hold the layer to."""
    import importlib

    scripts = os.path.join(_REPO, "scripts")
    sys.path.insert(0, scripts)
    try:
        return importlib.import_module("tpu_kernel_sweep")
    finally:
        sys.path.remove(scripts)


def _pairs(idx, gates, E, valid, first):
    return _sweep()._pairs_by_expert(idx, gates, E, valid, first)


def _rows_moved_around_the_kernel(*layer):
    return _sweep()._expert_ffn_around_the_kernel(*layer)


def _rows_moved_by_the_kernel_gated_by_xla(u, idx, gates, w13, w2,
                                           valid=None, first=None):
    """The row copies alone: the rows in by their ids and out to where they
    belong, `silu(a) * b` still XLA's."""
    import flax.linen as nn
    import jax.numpy as jnp
    from ray_tpu.models.lfm2_moe import _two_terms
    from ray_tpu.ops.grouped_matmul import grouped_matmul

    T, k = idx.shape
    order, sizes, kept = _pairs(idx, gates, w13.shape[0], valid, first)
    a, b = jnp.split(grouped_matmul(u, w13, sizes, _two_terms,
                                    rows=order % T), 2, axis=-1)
    y = grouped_matmul(nn.silu(a) * b, w2, sizes, _two_terms, to=order)
    y = y.reshape(k, T, *y.shape[1:])
    kept = kept.T[..., None, None]
    return jnp.sum(jnp.where(kept > 0, y, 0.0) * kept, axis=0).reshape(T, -1)


def _layer(T, k, E, routed, dtype, seed=0, d=64, f=32):
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((T, d)), jnp.float32),
            jnp.asarray(np.stack([rng.choice(routed, k, replace=False)
                                  for _ in range(T)]), jnp.int32),
            jax.nn.softmax(jnp.asarray(rng.standard_normal((T, k)),
                                       jnp.float32)),
            jnp.asarray(rng.standard_normal((E, d, 2 * f)) * 0.1, dtype),
            jnp.asarray(rng.standard_normal((E, f, d)) * 0.1, dtype))


# name -> (T, k, experts held, experts routed over, first, valid of a row).
# (Nothing here is jitted but the kernel's call: the cases of one shape
# share its lowerings, which are most of a case's seconds.)
_CASES = {
    # 150 pairs: two tiles and 22 rows of a third, every group's end inside
    # a tile
    "all_rows_valid": (50, 3, 8, 8, None, None),
    "some_rows_valid": (50, 3, 8, 8, None, lambda t: t % 7 != 3),
    # experts 4-11 of 16 are held: about half of the pairs have no group
    "pairs_held_elsewhere": (50, 3, 8, 16, 4, None),
    "held_elsewhere_and_some_valid": (50, 3, 8, 16, 4, lambda t: t % 5 != 1),
    # the router chose among four experts: four groups are empty
    "empty_groups": (50, 3, 8, 4, None, None),
    "every_row_invalid": (50, 3, 8, 8, None, lambda t: t < 0),
    # (the share holds experts 8-15, and the router chose among 0-7)
    "every_pair_held_elsewhere": (50, 3, 8, 8, 8, None),
    # 4 x 64 pairs: every tile full, T k a multiple of the tile
    "whole_tiles": (128, 2, 8, 8, None, None),
    # 14 pairs: less than a tile, and experts that take no row
    "less_than_a_tile": (7, 2, 8, 8, None, None),
}


@pytest.mark.parametrize("case, matrices", [
    (case, matrices) for case in _CASES for matrices in ("bfloat16", "float32")
    if matrices == "bfloat16" or case in ("some_rows_valid",
                                          "pairs_held_elsewhere")])
def test_the_kernel_moves_a_routed_layers_rows(case, matrices):
    """`expert_ffn` over rows that are and are not `valid`, a share with
    pairs held elsewhere, groups that are empty and groups that end inside
    a tile, pair rows that fill no whole tile, and calls in which no pair
    has a group: the row copies alone give the bits of the layer as it
    was, and the whole layer, gated inside the kernel, lies within the
    tolerance; the counts are the same."""
    import jax.numpy as jnp
    from ray_tpu.models.lfm2_moe import expert_ffn

    T, k, E, routed, first, valid = _CASES[case]
    args = _layer(T, k, E, routed, matrices)
    valid = None if valid is None else jnp.asarray(valid(np.arange(T)))
    want = np.asarray(_rows_moved_around_the_kernel(*args, valid, first))
    copied = np.asarray(_rows_moved_by_the_kernel_gated_by_xla(
        *args, valid, first))
    assert (copied.view(np.uint32) == want.view(np.uint32)).all()
    got, counts = expert_ffn(*args, valid, first)
    assert got.shape == (T, 64) and got.dtype == jnp.float32
    assert np.abs(np.asarray(got) - want).max() < TOL
    sizes = np.asarray(_pairs(*args[1:3], E, valid, first)[1])
    assert list(np.asarray(counts)) == [(sizes > 0).sum(), E, sizes.max(),
                                        sizes.sum()]
    if "every" in case:
        assert sizes.sum() == 0 and not np.asarray(got).any()
    else:
        assert np.abs(want).max() > 0.01


@pytest.mark.parametrize("case", ["some_rows_valid", "pairs_held_elsewhere",
                                  "every_row_invalid"])
def test_a_row_no_copy_wrote_is_dropped(monkeypatch, case):
    """The second product writes the rows of pairs that have a group and
    no other: what it never wrote holds anything.  Poisoned with NaN, those
    rows leave the layer's result as it was, and finite."""
    import jax.numpy as jnp
    from ray_tpu.models import lfm2_moe

    T, k, E, routed, first, valid = _CASES[case]
    args = _layer(T, k, E, routed, "bfloat16", seed=1)
    valid = None if valid is None else jnp.asarray(valid(np.arange(T)))
    want = np.asarray(lfm2_moe.expert_ffn(*args, valid, first)[0])
    real, poisoned = lfm2_moe.grouped_matmul, []

    def poison(x, w, sizes, two_terms, *, to=None, **how):
        got = real(x, w, sizes, two_terms, to=to, **how)
        if to is None:
            return got
        written = jnp.zeros(to.shape, bool).at[to].set(
            jnp.arange(to.shape[0]) < jnp.sum(sizes))
        poisoned.append(written)
        return jnp.where(written[:, None, None], got, jnp.nan)

    monkeypatch.setattr(lfm2_moe, "grouped_matmul", poison)
    got = np.asarray(lfm2_moe.expert_ffn(*args, valid, first)[0])
    assert len(poisoned) == 1 and not np.asarray(poisoned[0]).all()
    assert np.isfinite(got).all()
    assert (got.view(np.uint32) == want.view(np.uint32)).all()


def test_rows_come_in_by_id_and_go_out_to_their_place():
    """The product alone, at rows of 256 columns, which are copied in two
    parts of 128 lanes as the cells' are in 16 and 32: row `rows[i]` of x
    is sorted row i, a row of x read by several pairs and some by none;
    the result of sorted row i lies at row `to[i]`, in its parts; a row
    past the groups names a row of x still (a tile's rows are all copied)
    and its result is written nowhere."""
    import jax.numpy as jnp
    from ray_tpu.models.sambay import _two_terms
    from ray_tpu.ops import grouped_matmul as gm

    rng = np.random.default_rng(2)
    sizes = np.asarray([0, 70, 1, 0, 59], np.int32)     # 130 of 150 rows
    held, M, d = int(sizes.sum()), 150, 256
    x = jnp.asarray(rng.standard_normal((40, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((5, d, 128)) * 0.1, jnp.bfloat16)
    rows = rng.integers(0, 40, M).astype(np.int32)
    to = rng.permutation(M).astype(np.int32)
    want = np.asarray(gm.grouped_matmul(x[rows[:held]], w, jnp.asarray(sizes),
                                        _two_terms))
    got = np.asarray(gm.grouped_matmul(x, w, jnp.asarray(sizes), _two_terms,
                                       rows=jnp.asarray(rows)))
    assert got.shape == (M, 128)
    assert (got[:held].view(np.uint32) == want.view(np.uint32)).all()
    # (and against the plain product of the rows the ids name)
    group = np.repeat(np.arange(5), sizes)
    plain = np.einsum("rk,rkn->rn", np.asarray(x)[rows[:held]],
                      np.asarray(w, np.float32)[group])
    assert np.abs(got[:held] - plain).max() < 1e-3
    back_w = jnp.swapaxes(w, 1, 2)
    placed = np.asarray(gm.grouped_matmul(
        jnp.asarray(got), back_w, jnp.asarray(sizes), _two_terms,
        to=jnp.asarray(to)))
    back = np.asarray(gm.grouped_matmul(
        jnp.asarray(got), back_w, jnp.asarray(sizes), _two_terms))
    assert placed.shape == (M, 2, 128) == (M,) + gm._parts(d)
    assert (placed.reshape(M, d)[to[:held]].view(np.uint32)
            == back[:held].view(np.uint32)).all()


def test_a_row_lies_in_whole_tiles_where_it_is_copied_alone():
    """A copied row's parts: the cells' widths (2,048 and 4,096 columns)
    lie in 16 and 32 parts of a tile's 128 lanes, whole (8, 128) float32
    tiles; the tiny models' width of 64, which no chip sees, is one."""
    from ray_tpu.ops.grouped_matmul import _parts

    for width in (2048, 4096):
        parts, lanes = _parts(width)
        assert lanes == 128 and parts % 8 == 0 and parts * lanes == width
    assert _parts(64) == (1, 64)


@pytest.mark.parametrize("matrices", ["float32", "bfloat16"])
def test_split_columns_keep_the_plain_store(monkeypatch, matrices):
    """Where `_tiles` splits the columns (no cell's matrix: one that does
    not fit VMEM twice) a row's a and b lie a column tile apart: the
    kernel stores the plain product in pair order and XLA gates and places
    it, to the same layer within the tolerance."""
    import jax.numpy as jnp
    from ray_tpu.models.lfm2_moe import expert_ffn
    from ray_tpu.ops import grouped_matmul as gm

    T, k, E, routed, first, _ = _CASES["pairs_held_elsewhere"]
    args = _layer(T, k, E, routed, matrices, seed=3, f=128)
    valid = jnp.arange(T) % 7 != 3
    want = np.asarray(_rows_moved_around_the_kernel(*args, valid, first))
    calls, real = [], gm._grouped_call

    def call(x, w, sizes, src, dst, *, tiles, gated, **kw):
        calls.append((src is not None, dst is not None, gated, tiles[2]))
        return real(x, w, sizes, src, dst, tiles=tiles, gated=gated, **kw)

    monkeypatch.setattr(gm, "_grouped_call", call)
    whole = np.asarray(expert_ffn(*args, valid, first)[0])
    # (rows in by id, the gate inside | rows out to their place)
    assert calls == [(True, False, True, 256), (False, True, False, 64)]
    del calls[:]
    monkeypatch.setattr(gm, "_tiles", lambda k, n, b=2: (TILE, k, n // 2))
    split = np.asarray(expert_ffn(*args, valid, first)[0])
    assert calls == [(True, False, False, 128), (False, False, False, 32)]
    assert np.abs(whole - want).max() < TOL
    assert np.abs(split - want).max() < TOL
