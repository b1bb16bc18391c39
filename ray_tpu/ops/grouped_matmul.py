"""Grouped matrix product: rows sorted by group, each group against its
own matrix, in ONE call (a routed feed-forward's experts over the (row,
expert) pairs of a step: `models/lfm2_moe.py`), the rows brought in and
put back by the kernel itself.

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

The kernel moves a routed layer's rows itself (PR 54).  Until then XLA moved
them AROUND it: a gather laid each sorted pair's row of the layer's input
out, `silu(a) * b` read the first product's result and wrote it again half
as wide, and a second sort and gather brought the second product's rows
back to pair order, 2.7 GB a layer at Granite-4.0-H-Small's 2,048-token
prompt where the products need 0.4 and half of the rows belong to pairs no
kernel visit reads.  Now:

* rows in by id (`rows`): the input stays in HBM and the sorted pairs' row
  ids are scalar-prefetched; a visit's row tile is copied row by row into
  one of two VMEM slots, once a row TILE, the next tile's rows asked for
  before this one's are waited for (as `ops/paged_attention.py` hands a
  slot on), and waited for in ONE wait for the slot's bytes.  A tile no
  visit reaches moves nothing.
* `silu(a) * b` is the first product's last step (`gated`): with the
  columns whole a visit holds a row's a and b together and stores their
  gated product, half the columns.  (Where `_tiles` would split the
  columns the halves lie a tile apart: that shape keeps the plain store,
  and `grouped_matmul` has XLA gate and place it; no cell has one.)
* rows out to their place (`to`): the second product's tile is masked in
  in VMEM and, after the tile's last visit, sorted row i is copied to row
  `to[i]` of the result while the next tile is multiplied.  Rows of pairs
  with no group are never written.

A row that is copied alone lies in parts of 128 lanes (`_parts`): one row of
a (rows, width) float32 array is a sublane of width / 128 tiles, which
Mosaic copies no slice of, so the input is reshaped to (rows x parts, 128)
(a copy of T rows, not of k T) and the result comes back as (rows, parts,
128), which the gated sum reads as it lies.  In VMEM a slot of (64 x parts,
128) is reshaped to the (64, width) tile and back.  What the kernel is
made of is counted in host time too: a program that holds a routed layer
traces and lowers the kernel in every warm start, on a host a third as
fast as a builder's, so a count of loop turns that may be 0 stands where a
branch would and one reshape where a part at a time was loaded (as first
written, in PR 53's session, the kernel cost desk-closed 12 s of a 91-s
`setup_s`; as it stands 91.2 s beside the parent's 95.4, PERF.md, PR 54).

The whole routed layer (`lfm2_moe.expert_ffn`; `scripts/tpu_kernel_sweep.py
--ffn` on the parent's tree and on this one in one call, a trace's device ms,
the median of five, TPU v5 lite; PERF.md, PR 54; the layer, its two kernels
and what stands around them: the parent's tree | this tree, W1|W3 and W2
apart):

    call (pairs held)                   layer kernels around   layer  W1|W3     W2 around
    Granite-H-Small decode 48 (248)       0.988  0.940  0.048   0.974  0.626  0.318  0.030
    Granite-H-Small prompt 512 (2531)     2.041  1.502  0.538   1.636  0.966  0.544  0.127
    Granite-H-Small prompt 2048 (10209)   6.174  3.127  3.046   4.301  2.214  1.298  0.788
    Kimi-VL decode 64 (384)               1.548  1.503  0.044   1.543  1.001  0.508  0.034
    Kimi-VL prompt 512 (3072)             2.189  2.061  0.128   2.193  1.366  0.749  0.077
    Kimi-VL prompt 2048 (12288)           4.921  3.933  0.988   4.660  2.743  1.564  0.353
    Kimi-VL prompt 8192 (49152)          15.911 10.889  5.022  13.834  7.828  4.751  1.256
    LFM2 decode 16 (64)                   1.026  1.012  0.014   1.025  0.674  0.339  0.011
    LFM2 prompt 128 (512)                 1.757  1.705  0.052   1.758  1.135  0.579  0.044
    LFM2 prompt 256 (1024)                1.846  1.786  0.060   1.840  1.188  0.614  0.038
    LFM2 prompt 512 (2048)                2.127  2.029  0.098   2.127  1.346  0.718  0.063
    LFM2 prompt 1024 (4096)               2.618  2.445  0.173   2.620  1.621  0.895  0.105
    LFM2 prompt 4096 (16384)              6.862  5.130  1.732   6.032  3.520  2.056  0.456

A long prompt's layer is 12-30% shorter (what stood around the kernels fell
3.05 -> 0.79, 5.02 -> 1.26, 1.73 -> 0.46 ms; the kernels themselves grew by
0.38, 1.69 and 0.45 ms: 27-38 ns a pair row, copied in and out); no call is
slower by more than 0.2%, a decode call 0.2-1.4% faster: one path, no rule
on the number of pair rows.  Granite's share holds half of its pairs (20,480
made at 2,048 tokens), and the other half now moves nothing.

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


def _parts(width: int) -> tuple:
    """A row that the kernel copies alone, as it lies in HBM and in VMEM:
    (parts, lanes), its columns in parts of one tile's 128 lanes, so that
    a row of 1,024 columns or a multiple is whole (8, 128) float32 tiles
    and one copy.  (A row of a (rows, width) array is a sublane of width /
    128 tiles: Mosaic copies no such slice, and reads a part of every row
    of a tile by a strided load only where the lanes are 128.)  A width
    that is no multiple of 128 (the tests' tiny models', which only the
    interpreter sees) is one part."""
    return (width // 128, 128) if width % 128 == 0 else (1, width)


def _vmem_bytes(tm: int, k: int, n: int, tn: int, w_bytes: int) -> int:
    """What a call holds in VMEM: the row tile and the slab twice (the
    pipeline's two buffers, or the two slots the rows are copied into),
    the output block twice (or the two slots they are copied from), the
    terms once, the product of both terms and the sum of its halves."""
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


def _kernel(offsets, group_ids, tile_ids, *refs, two_terms, tm: int, tn: int,
            by_row: bool, to_row: bool, gated: bool, two: bool):
    """One (visit, column tile) of the grid.  w (k, tn): a slab of the
    visit's group.  The visit's row tile, (tm, k) float32: the block `x`,
    or (`by_row`) the scratch `x_tile`: row `src[i]` of `x` in HBM for the
    tile's row i, copied into one of two VMEM slots, the next tile's rows
    asked for before this one's are waited for, and the slot reshaped to
    the tile.  The tile's result, (tm, n) float32, masked in a visit at a
    time: the block `out`, or (`to_row`, the columns whole) the scratch
    `out_tile`, which after the tile's last visit is reshaped into a VMEM
    slot from which row i goes to row `dst[i]` of `out` in HBM while the
    next tile is multiplied.  A row that is copied alone lies in HBM and
    in a slot in its parts (`_parts`).  `gated`: the columns are whole and
    [a | b]; `silu(a) * b` is stored, half as wide.  terms: (2 tm, k)
    bfloat16 where the rows enter as two terms (`two`)."""
    refs = list(refs)
    src = refs.pop(0) if by_row else None
    dst = refs.pop(0) if to_row else None
    x, w, out = refs[:3]
    del refs[:3]
    terms = refs.pop(0) if two else None
    x_tile, x_buf, x_sem = refs[:3] if by_row else (x, None, None)
    out_tile, out_buf, out_sem = refs[-3:] if to_row else (out, None, None)
    visit, col = pl.program_id(0), pl.program_id(1)
    visits = pl.num_programs(0)
    tile = tile_ids[visit]
    slot = tile % 2         # of the two the rows come in through
    # (the next group's visit of the same row tile finds its rows there)
    new_tile = (col == 0) & ((visit == 0)
                             | (tile != tile_ids[jnp.maximum(visit - 1, 0)]))
    held = offsets[offsets.shape[0] - 1]    # rows of groups: the live ones

    # (Every branch and loop below is traced and lowered for each program
    # that holds a routed layer, on the host, in a warm start too: a count
    # of turns that may be 0 stands where a `pl.when` would.)
    def times(n, act):
        jax.lax.fori_loop(0, n, lambda i, carry: act(i) or carry, None)

    def of_row(row, buf):
        parts = buf.shape[-2] // tm
        return pl.ds(pl.multiple_of(row * parts, parts), parts)

    def row_in(t, r):
        return pltpu.make_async_copy(
            x.at[of_row(src[t * tm + r], x_buf)],
            x_buf.at[t % 2, of_row(r, x_buf)], x_sem.at[t % 2])

    def row_out(r):
        return pltpu.make_async_copy(
            out_buf.at[of_row(r, out_buf)],
            out.at[of_row(dst[tile * tm + r], out_buf)], out_sem)

    @pl.when(new_tile)
    def _bring():
        if by_row:
            # Every row of a tile that holds a live one is copied (a row of
            # no group names a row of x too), so a tile's copies are waited
            # for in ONE wait for the slot's bytes: this tile's were asked
            # for a tile ago (at the first visit, here), the next tile's
            # are asked for now.
            def ask(i):
                t = tile + i
                times(jnp.where(t * tm < held, tm, 0),
                      lambda r: row_in(t, r).start())

            jax.lax.fori_loop(jnp.where(visit == 0, 0, 1), 2,
                              lambda i, carry: ask(i) or carry, None)
            pltpu.make_async_copy(x_buf.at[slot], x_buf.at[slot],
                                  x_sem.at[slot]).wait()
            x_tile[...] = x_buf[slot].reshape(x_tile.shape)
        if two:
            terms[...] = two_terms(x_tile[...], 0)

    got = jnp.dot((terms if two else x_tile)[...], w[...],
                  preferred_element_type=jnp.float32)
    if two:
        got = got[:tm] + got[tm:]
    if gated:
        got = jax.nn.silu(got[:, :tn // 2]) * got[:, tn // 2:]
    wide = got.shape[1]
    group = group_ids[visit]
    row = tile * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, wide), 0)
    mine = (row >= offsets[group]) & (row < offsets[group + 1])
    at = pl.ds(pl.multiple_of(col * wide, wide), wide)
    out_tile[:, at] = jnp.where(mine, got, out_tile[:, at])
    if not to_row:
        return
    last = visit == visits - 1

    @pl.when(last | (tile != tile_ids[jnp.minimum(visit + 1, visits - 1)]))
    def _send():
        # The tile before this one (a whole one) went out while this one
        # was multiplied: its copies have left the slot before this tile's
        # rows are laid into it, each in its parts, and asked for; the
        # call's last tile is waited for where it is sent.
        live = jnp.clip(held - tile * tm, 0, tm)
        times(jnp.minimum(tile, 1), lambda _: pltpu.make_async_copy(
            out_buf, out_buf, out_sem).wait())
        out_buf[...] = out_tile[...].reshape(out_buf.shape)
        times(live, lambda r: row_out(r).start())
        times(jnp.where(last, live, 0), lambda r: row_out(r).wait())


# (jit: the layers of a model share one lowering; a profile names the call)
@functools.partial(jax.jit, static_argnames=("two_terms", "tiles", "gated",
                                             "interpret"))
def _grouped_call(x, w, sizes, src, dst, *, two_terms, tiles: tuple,
                  gated: bool, interpret: bool):
    """The kernel over M sorted rows, M a multiple of the row tile.  `src`
    (M,) int32: the row of x (R, k) each is, or None: x (M, k) holds them
    as they lie.  `dst` (R,) int32: the row of the result each goes to (the
    columns whole), the result (R, parts, lanes) with each row in its
    parts, or None: the result is (M, n), in the rows' order."""
    tm, k, tn = tiles
    M = x.shape[0] if src is None else src.shape[0]
    G, _, n = w.shape
    (offsets, group_ids, tile_ids), visits = make_group_metadata(
        group_sizes=sizes, m=M, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=G, visit_empty_groups=False)
    two = w.dtype != jnp.float32
    by_row, to_row = src is not None, dst is not None
    assert tn == n or not (gated or to_row), (tiles, n)
    wide = n // 2 if gated else n
    parts, lanes = _parts(wide)
    ids = [a for a in (src, dst) if a is not None]
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    tile_of = lambda v, c, o, g, t, *ids: (t[v], 0)     # noqa: E731
    # (a tile as a matrix, beside what its rows are copied through: two
    # slots on the way in, one on the way out)
    def through(width, slots):
        parts, lanes = _parts(width)
        return [pltpu.VMEM((tm, width), jnp.float32),
                pltpu.VMEM(slots + (tm * parts, lanes), jnp.float32),
                pltpu.SemaphoreType.DMA(slots)]

    got = pl.pallas_call(
        functools.partial(_kernel, two_terms=two_terms, tm=tm, tn=tn,
                          by_row=by_row, to_row=to_row, gated=gated, two=two),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + len(ids),
            grid=(visits, n // tn),
            in_specs=[
                in_hbm if by_row else pl.BlockSpec((tm, k), tile_of),
                pl.BlockSpec((None, k, tn),
                             lambda v, c, o, g, t, *ids: (g[v], 0, c)),
            ],
            out_specs=in_hbm if to_row else pl.BlockSpec((tm, wide), tile_of),
            scratch_shapes=[pltpu.VMEM((2 * tm, k), jnp.bfloat16)] * two
            + through(k, (2,)) * by_row + through(wide, ()) * to_row),
        out_shape=jax.ShapeDtypeStruct(
            (dst.shape[0] * parts, lanes) if to_row else (M, wide),
            jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * (1 + two) * M * k * n,
            transcendentals=M * wide * gated,
            bytes_accessed=4 * M * (k + wide) + G * k * n * w.dtype.itemsize),
        interpret=interpret,
    )(offsets, group_ids, tile_ids, *ids,
      x.reshape(-1, _parts(k)[1]) if by_row else x, w)
    return got.reshape(-1, parts, lanes) if to_row else got


def grouped_matmul(x, w, sizes, two_terms, *, rows=None, to=None,
                   gated: bool = False):
    """M rows sorted by group, each against its group's matrix of w (G, k,
    n); sizes (G,) int32, the rows of each group (rows past their sum
    belong to no group: nothing is multiplied for them or written).

    rows (M,) int32: sorted row i is row `rows[i]` of x (R, k), and the
             kernel brings it in itself (every id a row of x, those past
             the groups too); None: x (M, k) holds the rows as
             they lie (with `to` None too that is `rows = arange(M)`: the
             form the tests call).
    to   (M,) int32, a permutation: the result of sorted row i is written
             to row `to[i]`, and the result comes back as (M, parts,
             lanes), each row in the parts it was copied in (`_parts`; a
             reshape to (M, n) is a copy of every row: reduce first);
             None: (M, n), row i the result of sorted row i.
    gated:   w is [A | B] and the result is `silu(x A) * (x B)`, n / 2 wide.

    The columns are whole at every cell's call (`_tiles`), and the kernel
    gates and places what it stores.  Where `_tiles` splits the columns a
    row's a and b lie a column tile apart and its parts in several: that
    shape keeps the plain store, and XLA gates and places the result.

    -> float32.  x is taken in float32; where w is bfloat16 a row tile
    enters the product as `two_terms(tile, 0)`, the caller's
    `sambay._two_terms`: its two bfloat16 terms, stacked."""
    tiles = _tiles(*w.shape[1:], w.dtype.itemsize)
    whole = tiles[2] == w.shape[2]
    x = x.astype(jnp.float32)
    if rows is None and to is None:
        rows = jnp.arange(x.shape[0], dtype=jnp.int32)
    M = x.shape[0] if rows is None else rows.shape[0]
    short = -M % tiles[0]       # (an id past the groups names a row of x too)
    if rows is None:
        x = jnp.pad(x, ((0, short), (0, 0)))
    else:
        rows = jnp.pad(rows.astype(jnp.int32), (0, short))
    got = _grouped_call(
        x, w, sizes.astype(jnp.int32), rows,
        to.astype(jnp.int32) if whole and to is not None else None,
        two_terms=two_terms, tiles=tiles, gated=gated and whole,
        interpret=_interpret_mode())
    if whole:
        return got if to is not None else got[:M]
    if gated:
        a, b = jnp.split(got, 2, axis=-1)
        got = jax.nn.silu(a) * b
    if to is None:
        return got[:M]
    return jnp.zeros_like(got[:M]).at[to].set(got[:M]).reshape(
        M, *_parts(got.shape[1]))
