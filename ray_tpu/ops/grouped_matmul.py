"""Grouped matrix product: rows sorted by group, each group against its
own matrix, in ONE call (a routed feed-forward's experts over the (row,
expert) pairs of a step: `models/lfm2_moe.py`).

A Pallas kernel of this repo's own, on the scheme of jax's `megablox.gmm`
(whose group metadata it imports): the (group, row tile) visits come from
the group sizes by scalar prefetch, so only the groups that hold a row are
visited and only the row tiles that hold one: a decode step of 16 rows
streams the 40 experts its rows chose and not all 64, and a padded prompt
pays for its real tokens.  Rows of another group are masked at the store;
rows past the last group are never written.

Where the matrices are bfloat16 the kernel takes the float32 rows as they
are and makes an activation's two bfloat16 terms ITSELF (the caller's
`sambay._two_terms`: the value with the low 16 bits of its float32 form
cleared, and what that left), once a row tile, into VMEM: both terms go
through the matrix unit against the same slab of the group's matrix, each
accumulated in float32 over the whole contraction, and their sum is
stored.  M float32 rows in, M out, bit for bit what `megablox.gmm` gives
over the two terms as two adjacent rows of a group with the two results
added (as `models/lfm2_moe.py` called it until PR 47, laying the rows out
around it: a `reshape` line of 0.55 s in a traced slot of
`kimivl-serve-pages-closed`).  float32 matrices (the tests' tiny models)
take the rows in one term.

The visits are the OUTER grid axis and a visit's column tiles the inner
one (`megablox.gmm` has the columns outermost, and reads a row tile again
for each of them): x is read once a row tile, its terms are made once, and
the output block is a whole row of column tiles, (row tile, n) float32,
which stays in VMEM across a visit's column tiles and across the next
group's visit of the same row tile.  With the columns whole the slab's
block is the group's whole matrix, and the pipeline brings it in once a
GROUP, however many row tiles the group spans.

The tiles (`_tiles`) rest on the kernel's device time from a profiler
trace, `scripts/tpu_kernel_sweep.py --gmm` on a TPU v5 lite (PERF.md, PR
47; ms, 64 groups, the contraction whole; beside `megablox.gmm` over the
doubled rows at the tiles PR 46 gave it; every tiling gave the same bits
as that call):

    call (float32 rows a group)   doubled  64 rows, the columns  whole, rows
                                     rows 512|256 halves  whole    128     32
    Kimi-VL decode 64 (6)     W1|W3 1.019  1.064  1.038  1.009  1.030  1.008
                              W2    0.532  0.531  0.528  0.509  0.528  0.509
    Kimi-VL prompt 512 (48)   W1|W3 1.781  1.840  1.789  1.422  1.418  1.413
                              W2    0.868  0.915  0.914  0.742  0.737  0.746
    Kimi-VL prompt 2048 (192) W1|W3 3.476  4.381  4.248  2.586  2.638  2.568
                              W2    1.662  2.210  2.225  1.348  1.387  1.377
    Kimi-VL prompt 8192 (768) W1|W310.264 14.614 14.160  7.186  7.190  7.187
                              W2    4.243  7.549  7.471  3.705  3.712  3.901
    LFM2 decode 16 (1)        W1|W3 0.674  0.669  0.672  0.676  0.690  0.672
                              W2    0.344  0.342  0.340  0.339  0.352  0.337
    LFM2 prompt 128 (8)       W1|W3 1.178  1.199  1.202  1.146  1.157  1.142
                              W2    0.600  0.605  0.612  0.578  0.592  0.578
    LFM2 prompt 256 (16)      W1|W3 1.284  1.315  1.318  1.211  1.219  1.228
                              W2    0.668  0.677  0.673  0.614  0.626  0.627
    LFM2 prompt 512 (32)      W1|W3 1.536  1.646  1.650  1.388  1.389  1.403
                              W2    0.812  0.839  0.848  0.712  0.715  0.739
    LFM2 prompt 1024 (64)     W1|W3 1.918  2.226  2.230  1.700  1.682  1.688
                              W2    1.021  1.156  1.154  0.884  0.870  0.885
    LFM2 prompt 4096 (256)    W1|W3 3.853  5.824  5.830  3.381  3.426  3.411
                              W2    2.083  3.190  3.217  1.746  1.781  1.810

One tiling, 64 float32 rows and the columns whole, is the fastest or within
1% of it at every call; at 256 rows every call is 10-100% slower, and with
the columns in tiles a prompt pays for its slabs a visit (14.6 against 7.2
ms).  The whole routed layer (`lfm2_moe.expert_ffn`, the same trace),
parent -> this kernel: 1.699 -> 1.548 ms at Kimi-VL's decode call, 47.94 ->
15.91 at 8,192 tokens; 1.099 -> 1.026 at LFM2's, 16.90 -> 6.86 at 4,096.

On CPU (tests) the kernel runs in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from ray_tpu.ops.attention import _interpret_mode

# float32 rows of a row tile: two tiles of the matrix unit's 128 rows, one a
# term.  By the sweep above nothing is gained over it and much lost under
# it or at 256 (a visit multiplies the whole tile, whatever the group owns).
_TILE_ROWS = 64
# What the kernel asks to hold in VMEM (of a v5e's 128 MiB; the compiler's
# own limit is 16 MiB, which a whole matrix of either cell, twice, passes).
_VMEM_LIMIT = 48 * 2 ** 20


def _vmem_bytes(tm: int, k: int, n: int, tn: int, w_bytes: int) -> int:
    """What a call holds in VMEM: the row tile and the slab twice (the
    pipeline's two buffers), the output block twice, the terms once, the
    product of both terms and the sum of its halves."""
    return (2 * tm * k * 4 + 2 * k * tn * w_bytes + 2 * tm * n * 4
            + 2 * tm * k * 2 + 3 * tm * tn * 4)


def _tiles(k: int, n: int, w_bytes: int = 2) -> tuple:
    """Tiles of the product (float32 rows, contraction, columns), from the
    matrices' static shape and type alone.

    The kernel visits every (group, row tile) pair that shares a row and
    there multiplies the WHOLE row tile, both terms, by a (k, columns) slab
    of the group's matrix: a tile far taller than the rows a group holds is
    operations on rows that are masked away, so the tile is 64 rows for a
    decode step (1-6 rows a group) and for a prompt (8-768) alike.  The
    contraction is whole, so a row's result is the same bits at every
    tile.  The columns are whole too wherever the matrix fits VMEM twice
    (every matrix of both cells: 11.5 and 12.6 MB): the slab's block is
    then the GROUP's, and the pipeline brings it in once a group, not once
    a visit, however many row tiles the group spans.  A wider matrix goes
    in the widest column tile, a multiple of 128, that divides it and
    fits (its slab comes in again at every visit: no cell has one)."""
    cols = next((c for c in range(n, 127, -128) if n % c == 0 and _vmem_bytes(
        _TILE_ROWS, k, n, c, w_bytes) <= _VMEM_LIMIT), n)
    return _TILE_ROWS, k, cols


def _kernel(offsets, group_ids, tile_ids, x, w, out, *terms, two_terms,
            tm: int, tn: int):
    """One (visit, column tile) of the grid.  x (tm, k) float32: the
    visit's row tile; w (k, tn): a slab of the visit's group; out (tm, n)
    float32: the row tile's whole output; terms: (2 tm, k) bfloat16 where
    the rows enter as two terms, else nothing."""
    visit, col = pl.program_id(0), pl.program_id(1)
    if terms:
        (terms,) = terms
        before = jnp.maximum(visit - 1, 0)

        # (the next group's visit of the same row tile finds them made)
        @pl.when((col == 0) & ((visit == 0)
                               | (tile_ids[visit] != tile_ids[before])))
        def _split():
            terms[...] = two_terms(x[...], 0)

        both = jnp.dot(terms[...], w[...],
                       preferred_element_type=jnp.float32)
        got = both[:tm] + both[tm:]
    else:
        got = jnp.dot(x[...], w[...], preferred_element_type=jnp.float32)
    group = group_ids[visit]
    row = tile_ids[visit] * tm + jax.lax.broadcasted_iota(
        jnp.int32, (tm, tn), 0)
    mine = (row >= offsets[group]) & (row < offsets[group + 1])
    at = pl.ds(pl.multiple_of(col * tn, tn), tn)
    out[:, at] = jnp.where(mine, got, out[:, at])


# (jit: the layers of a model share one lowering; a profile names the call)
@functools.partial(jax.jit,
                   static_argnames=("two_terms", "tiles", "interpret"))
def _grouped_call(x, w, sizes, *, two_terms, tiles: tuple, interpret: bool):
    tm, k, tn = tiles
    M, (G, _, n) = x.shape[0], w.shape
    (offsets, group_ids, tile_ids), visits = make_group_metadata(
        group_sizes=sizes, m=M, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=G, visit_empty_groups=False)
    two = w.dtype != jnp.float32
    return pl.pallas_call(
        functools.partial(_kernel, two_terms=two_terms, tm=tm, tn=tn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(visits, n // tn),
            in_specs=[
                pl.BlockSpec((tm, k), lambda v, c, o, g, t: (t[v], 0)),
                pl.BlockSpec((None, k, tn),
                             lambda v, c, o, g, t: (g[v], 0, c)),
            ],
            out_specs=pl.BlockSpec((tm, n), lambda v, c, o, g, t: (t[v], 0)),
            scratch_shapes=[pltpu.VMEM((2 * tm, k), jnp.bfloat16)] * two),
        out_shape=jax.ShapeDtypeStruct((M, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * (1 + two) * M * k * n, transcendentals=0,
            bytes_accessed=4 * M * (k + n) + G * k * n * w.dtype.itemsize),
        interpret=interpret,
    )(offsets, group_ids, tile_ids, x, w)


def grouped_matmul(x, w, sizes, two_terms):
    """x (M, k), rows sorted by group; w (G, k, n); sizes (G,) int32, the
    rows of each group (rows past their sum belong to no group and come
    back as anything) -> (M, n) float32.  x is taken in float32; where w
    is bfloat16 a row tile enters the product as `two_terms(tile, 0)`,
    the caller's `sambay._two_terms`: its two bfloat16 terms, stacked."""
    M = x.shape[0]
    tiles = _tiles(*w.shape[1:], w.dtype.itemsize)
    x = jnp.pad(x.astype(jnp.float32), ((0, -M % tiles[0]), (0, 0)))
    return _grouped_call(x, w, sizes.astype(jnp.int32), two_terms=two_terms,
                         tiles=tiles, interpret=_interpret_mode())[:M]
