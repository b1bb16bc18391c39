"""Grouped matrix product: rows sorted by group, each group against its
own matrix, in ONE call (a routed feed-forward's experts over the (row,
expert) pairs of a step: `models/lfm2_moe.py`).

It is the Pallas `megablox.gmm` of jax, which visits only the groups that
hold a row and only the row tiles that hold one: a decode step of 16 rows
streams the 40 experts its rows chose and not all 64, and a padded prompt
pays for its real tokens.  Read on the chip against `jax.lax.ragged_dot`
at a decode step's and a prompt's shapes and at eight tilings (PERF.md,
PR 42): `gmm` was 25-35% faster at every shape, and nothing chooses
between them.

On CPU (tests) the kernel runs in interpret mode.
"""

from __future__ import annotations

import jax.numpy as jnp

from ray_tpu.ops.attention import _interpret_mode

_TILE_ROWS = 128


def _tiles(rows: int, k: int, n: int) -> tuple:
    """Tiles of the product (rows, contraction, columns): the whole
    contraction and 512 columns, so that a group's matrix streams through
    in slabs of 1.5-2 MB, over one tile of 128 rows (a decode step) or
    tiles of 256 (a prompt).  Where 512 does not divide the columns, the
    widest multiple of 128 under it that does (2,816 = 11 x 256)."""
    cols = next((c for c in (512, 384, 256, 128) if n % c == 0), min(n, 512))
    return (_TILE_ROWS if rows <= _TILE_ROWS else 256), k, cols


def grouped_matmul(x, w, sizes):
    """x (M, k), rows sorted by group; w (G, k, n); sizes (G,) int32, the
    rows of each group (rows past their sum belong to no group and come
    back as anything) -> (M, n) float32.  x is taken in w's type."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    tiles = _tiles(x.shape[0], *w.shape[1:])
    return gmm(
        jnp.pad(x.astype(w.dtype), ((0, -x.shape[0] % tiles[0]), (0, 0))),
        w, sizes, jnp.float32, tiles,
        interpret=_interpret_mode())[: x.shape[0]]
