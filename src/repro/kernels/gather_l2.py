"""Fused gather + squared-L2 Pallas kernels (scalar-prefetch DMA gather —
DESIGN.md §5, blocked tiling contract §8).

The KHI engine's expansion step gathers candidate rows ``corpus[idx]`` from
HBM and immediately reduces them against the query — on TPU the idiomatic
form is a *scalar-prefetched* index stream driving the DMA source, so each
candidate row moves HBM->VMEM exactly once and no (B, C, d) gather is ever
materialized in HBM. Two forms share that contract:

  * ``gather_l2_raw`` — the semantics-bearing validation form: grid (B, C),
    the input BlockSpec's index_map selects one (1, d) corpus row per grid
    step. One DMA descriptor and one scalar reduction per candidate.
  * ``gather_l2_blocked_raw`` — the production form: grid (B, C/C_BLK),
    corpus stays in ``ANY`` (compiler-chosen, HBM at size) memory and each
    grid step issues C_BLK *overlapping* row DMAs into a (C_BLK, d) VMEM
    scratch tile, waits once, then runs ONE vectorized (C_BLK, d) -> (C_BLK,)
    reduction. The wide-frontier engine feeds this C = E·c_n candidates per
    hop, so a hop is a handful of fat tiles instead of C scalar grid steps.

Both accumulate distances in f32 (bf16 corpora supported) and both compute
``sum((q - row)^2)`` with the same per-row reduction shape, so their outputs
are bitwise identical — pinned by tests/test_kernels.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .blocks import (gather_rows, lane_block, row_block, tile_rows,
                     tile_pad)

__all__ = ["gather_l2_kernel", "gather_l2_raw", "gather_l2_blocked_kernel",
           "gather_l2_blocked_raw"]


def gather_l2_kernel(idx_ref, corpus_ref, q_ref, o_ref):
    """Grid (B, C): step (i, j) holds corpus row idx[i, j] and query row i."""
    j = pl.program_id(1)
    d = q_ref[...].astype(jnp.float32) - corpus_ref[...].astype(jnp.float32)
    val = jnp.sum(d * d)
    o_ref[:, pl.dslice(j, 1)] = val[None, None]


def gather_l2_raw(idx: jax.Array, corpus: jax.Array, q: jax.Array,
                  *, interpret: bool = False) -> jax.Array:
    """idx (B, C) int32, corpus (N, d), q (B, d) -> (B, C) f32."""
    B, C = idx.shape
    N, D = corpus.shape
    return pl.pallas_call(
        gather_l2_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, C),
            in_specs=[
                # corpus row selected by the prefetched index stream
                pl.BlockSpec((1, D), lambda i, j, idx_ref: (idx_ref[i, j], 0)),
                # query row for this i (re-used across all j)
                pl.BlockSpec((1, D), lambda i, j, idx_ref: (i, 0)),
            ],
            out_specs=pl.BlockSpec((1, C), lambda i, j, idx_ref: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, C), jnp.float32),
        interpret=interpret,
    )(idx, corpus, q)


def gather_l2_blocked_kernel(idx_ref, corpus_ref, q_ref, o_ref, tiles_ref,
                             rows_ref, sems_ref):
    """Grid (B, C/C_BLK): step (i, j) gathers rows idx[i, j*C_BLK :
    (j+1)*C_BLK] into a (C_BLK, d) VMEM tile via C_BLK overlapping tile
    DMAs (``blocks.gather_rows``), then reduces the whole tile against
    query row i in one shot."""
    gather_rows(idx_ref, corpus_ref, tiles_ref, rows_ref, sems_ref)
    d = q_ref[...].astype(jnp.float32) - rows_ref[...]
    o_ref[...] = jnp.sum(d * d, axis=-1)[None, :]


def gather_l2_blocked_raw(idx: jax.Array, corpus: jax.Array, q: jax.Array,
                          *, c_blk: int = 128,
                          interpret: bool = False) -> jax.Array:
    """Blocked form of ``gather_l2_raw`` — same signature and bitwise-equal
    output, C_BLK candidate rows per grid step.

    Tiling contract (DESIGN.md §8): ``idx`` is padded to a multiple of
    ``c_blk`` with index 0 (any in-range row — the padded lanes' distances
    are sliced off before returning, mirroring the engine's convention that
    invalid slots get their distances overwritten upstream); the corpus is
    DMA'd tile by tile (padded by ``kernels.blocks.tile_pad``, a copy only
    when its row count is not a tile multiple)."""
    B, C = idx.shape
    N, D = corpus.shape
    c_blk = min(c_blk, C)
    pad = (-C) % c_blk
    if pad:
        idx = jnp.pad(idx, ((0, 0), (0, pad)))
    n_blk = (C + pad) // c_blk
    out = pl.pallas_call(
        gather_l2_blocked_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, n_blk),
            in_specs=[
                # corpus stays whole in compiler-chosen (HBM) memory; the
                # kernel DMAs the tiles holding the selected rows itself
                pl.BlockSpec(memory_space=pl.ANY),
                row_block(D),
            ],
            out_specs=lane_block(c_blk, lambda j: j),
            scratch_shapes=[
                pltpu.VMEM((c_blk, tile_rows(corpus.dtype), D),
                           corpus.dtype),
                pltpu.VMEM((c_blk, D), jnp.float32),    # the picked rows
                pltpu.SemaphoreType.DMA((c_blk,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, 1, n_blk * c_blk), jnp.float32),
        interpret=interpret,
    )(idx, tile_pad(corpus), q[:, None])
    return out[:, 0, :C]
