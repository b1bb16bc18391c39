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

The tiles (`_tiles`) follow the rows a GROUP can hold, rows / groups of the
call's static shapes.  Device time of the kernel alone from a profiler
trace, `scripts/tpu_kernel_sweep.py --gmm` on a TPU v5 lite (PERF.md, PR
46; ms at row tiles of 128 / 256, the contraction whole, 64 groups):

    rows a group (the call)                columns   128      256
      2  (LFM2, decode, 16 slots)    W1|W3   512     0.683    0.756
     12  (Kimi-VL, decode, 64 slots) W1|W3   256     1.081    1.285
     12                              W1|W3  1408     1.019    (no room)
     12                              W2      512     0.565    0.617
     16  (LFM2, prompt of 128)       W1|W3   512     1.236    1.297
     32  (LFM2, prompt of 256)       W1|W3   512     1.334    1.370
     64  (LFM2, prompt of 512)       W1|W3   512     1.548    1.575
     96  (Kimi-VL, prompt of 512)    W2      512     0.921    0.890
    128  (LFM2, prompt of 1,024)     W1|W3   512     2.014    1.934
    512  (LFM2, prompt of 4,096)     W1|W3   512     3.893    3.864
   1536  (Kimi-VL, prompt of 8,192)  W2      512     4.698    4.293

Up to 64 rows a group: tiles of 128 rows, and W1|W3's 2,816 columns in two
halves; over that: 256 rows, and the columns in 256s.  (In halves at 128
rows a PROMPT's W1|W3 is faster alone too, 7.25 against 10.16 ms at 8,192
tokens; but with 128-row tiles, whatever the columns, the whole 8,192-token
prefill compiles to 1.1 GB more of temporaries, 15.34 GB with the cell's
state resident: not taken.)  Tiles of 32 and 64 rows are no faster than
128 anywhere: the slab's DMA bounds a visit from 128 down, and every tile
edge that cuts a group brings its slab in again.

On CPU (tests) the kernel runs in interpret mode.
"""

from __future__ import annotations

import jax.numpy as jnp

from ray_tpu.ops.attention import _interpret_mode

_TILE_ROWS = 128
# Rows a group can hold up to which the small row tile is the faster one.
_FEW_ROWS_A_GROUP = 64
# Elements of a group's matrix of which HALF the columns, in bfloat16 and
# twice (the kernel's two buffers), fit the chip's VMEM beside a tile of
# 128 rows, its result and the accumulator (2,048 x 2,816: 2 x 5.8 MB).
_HALF_FITS = 6 * 2 ** 20


def _tiles(rows: int, groups: int, k: int, n: int) -> tuple:
    """Tiles of the product (rows, contraction, columns), from the call's
    static shapes alone.

    `gmm` visits every (group, row tile) pair that shares a row and there
    multiplies the WHOLE row tile by a (k, columns) slab of the group's
    matrix, so a tile far taller than a group is operations on rows that
    are masked away: where a group can hold few rows (a decode step's
    2-12, whatever the rows of the call) the tile is 128 rows, where it can
    hold more a prompt's 256.  The contraction is whole, so a row's result
    is the same bits at every tile.  The columns go in tiles of 512, slabs
    of 1.5-2 MB, or where 512 does not divide them in the widest multiple
    of 128 under it that does (2,816 = 11 x 256).  x is read again for
    every column tile, so such columns go in two halves (1,408 = 11 x 128)
    where VMEM has the room: beside 128 rows, no more."""
    cols = next((c for c in (512, 384, 256, 128) if n % c == 0), min(n, 512))
    if rows > _FEW_ROWS_A_GROUP * groups:
        return 256, k, cols
    if cols < min(n, 512) and n % 256 == 0 and k * n <= _HALF_FITS:
        cols = n // 2
    return _TILE_ROWS, k, cols


def grouped_matmul(x, w, sizes):
    """x (M, k), rows sorted by group; w (G, k, n); sizes (G,) int32, the
    rows of each group (rows past their sum belong to no group and come
    back as anything) -> (M, n) float32.  x is taken in w's type."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    tiles = _tiles(x.shape[0], *w.shape)
    return gmm(
        jnp.pad(x.astype(w.dtype), ((0, -x.shape[0] % tiles[0]), (0, 0))),
        w, sizes, jnp.float32, tiles,
        interpret=_interpret_mode())[: x.shape[0]]
