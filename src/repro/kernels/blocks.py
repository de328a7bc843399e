"""Block and row-gather helpers shared by the gather and scan kernels.

Two Mosaic rules shape every kernel here:

  * the last two dimensions of a block must be divisible by (8, 128) or
    equal the array's own. A ``(1, X)`` block over a ``(B, X)`` operand
    breaks that rule whenever B > 1, so per-query operands travel as
    ``(B, 1, X)`` with a squeezed leading block dimension: the block's last
    two dimensions ``(1, X)`` then equal the array's, and the kernel body
    still sees the same ``(1, X)`` ref (same reduction shapes, same bits);
  * a DMA may only move whole HBM tiles along the second-minor axis: a
    single row of an ``(N, X)`` array cannot be sliced out. A row gather
    therefore DMAs the ``s``-row slice that starts at the row's tile
    boundary (``s`` = rows per tile) and picks the row out in VMEM
    (``gather_rows``). The pick copies the row's values exactly, so
    distances keep their bits. (A reshape to ``(N / s, s, X)`` would say
    the same, but XLA materializes it: a whole-corpus copy per call.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["row_block", "lane_block", "col_block", "tile_rows", "tile_pad",
           "gather_rows"]


def row_block(width: int) -> pl.BlockSpec:
    """Query i's whole ``(1, width)`` row of a ``(B, 1, width)`` operand at
    grid step (i, ...). Extra index_map arguments (scalar-prefetch refs,
    further grid axes) are ignored."""
    return pl.BlockSpec((None, 1, width), lambda i, *_: (i, 0, 0))


def lane_block(width: int, col) -> pl.BlockSpec:
    """Query i's lane block ``col(j)`` of width ``width`` in a
    ``(B, 1, n * width)`` operand at grid step (i, j, ...). ``width`` must
    be a multiple of 128 or the array's whole last dimension."""
    return pl.BlockSpec((None, 1, width), lambda i, j, *_: (i, 0, col(j)))


def col_block(height: int, width: int = 1) -> pl.BlockSpec:
    """Query i's ``(height, width)`` block j of a ``(B, n * height, width)``
    operand at grid step (i, j, ...): ``height`` must be a multiple of 8
    or the array's whole second dimension."""
    return pl.BlockSpec((None, height, width), lambda i, j, *_: (i, j, 0))


def tile_rows(dtype) -> int:
    """Rows per (8, 128) 32-bit HBM tile: 8 for f32, 16 for bf16, 32 for
    int8 (narrow types pack along the second-minor axis)."""
    return 32 // np.dtype(dtype).itemsize


def tile_pad(x: jax.Array) -> jax.Array:
    """``(N, X)`` with N padded up to a multiple of ``s = tile_rows``, so
    that every row's whole tile can be DMA'd. Free when ``s`` divides N,
    as it does for every corpus ``engine.device_put_index`` makes;
    otherwise a copy of the whole array on every call, with pad rows no
    id ever selects (direct kernel callers with odd N, delta buffers)."""
    pad = (-x.shape[0]) % tile_rows(x.dtype)
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def gather_rows(idx_ref, src_ref, tiles_ref, rows_ref, sems_ref) -> None:
    """Row gather for grid step (i, j) of a (B, C / C_BLK) grid.

    For each of the C_BLK ids ``idx[i, j*C_BLK + r]`` in the scalar-prefetched
    ``idx_ref`` (-1 reads row 0), DMA the s-row tile of the tile-padded
    ``src_ref`` (``tile_pad``) that holds the row into slot r of
    ``tiles_ref`` (C_BLK, s, X) — every copy in flight at once — then, as
    each copy lands, write the row itself, widened to f32, into row r of
    ``rows_ref`` (C_BLK, X)."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    c_blk, s, width = tiles_ref.shape

    def row(r):
        return jnp.maximum(idx_ref[i, j * c_blk + r], 0)

    def copy(r):
        base = pl.multiple_of(row(r) // s * s, s)
        return pltpu.make_async_copy(src_ref.at[pl.ds(base, s)],
                                     tiles_ref.at[r], sems_ref.at[r])

    def issue(r, carry):
        copy(r).start()
        return carry

    def pick(r, carry):
        copy(r).wait()
        off = row(r) % s
        if tiles_ref.dtype == jnp.float32:
            rows_ref[pl.ds(r, 1), :] = tiles_ref[r, pl.ds(off, 1), :]
        else:
            # Mosaic cannot load one row of a packed (bf16/int8) tile at a
            # dynamic offset: widen the tile and take the row by a max over
            # it and -inf, which is exact
            tile = tiles_ref[r].astype(jnp.float32)          # (s, X)
            hit = jax.lax.broadcasted_iota(jnp.int32, (s, width), 0) == off
            rows_ref[pl.ds(r, 1), :] = jnp.max(
                jnp.where(hit, tile, -jnp.inf), axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, c_blk, issue, 0)
    jax.lax.fori_loop(0, c_blk, pick, 0)
