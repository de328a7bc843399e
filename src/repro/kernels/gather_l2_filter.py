"""Predicate-fused gather + squared-L2 Pallas kernel (DESIGN.md §9).

The KHI engine's scoring step evaluates candidate rows against BOTH the
query vector (squared L2) and the query's range predicate
``all(qlo <= attrs[id] <= qhi)``.  The unfused backends leave the
predicate to a separate XLA gather of ``di.attrs``; this kernel extends
the blocked scalar-prefetched gather (``kernels.gather_l2``) to DMA each
candidate's **attribute row alongside its vector row** and evaluate the
predicate in-kernel, emitting ``+inf`` for out-of-range rows — one pass
over the id stream, no separately materialized attrs gather at the
scoring site.

Contract extensions over ``gather_l2_blocked_raw``:

  * ``idx`` may contain ``-1`` (the engine's pad/invalid lanes): those
    lanes DMA row 0 (any in-range row) and emit ``+inf`` — the kernel
    natively consumes the engine's -1-padded candidate buffers, so the
    caller-side ``where(valid, d, inf)`` overwrite disappears;
  * per-query bounds ``qlo``/``qhi`` ride in as ``(B, m)`` blocked inputs,
    and the ids a second time as a VMEM column block: Mosaic loads only
    scalars from SMEM, and the pad test is a vector compare. Vector rows
    arrive tile-wise and are picked out in VMEM (``kernels.blocks``);
  * finite lanes are **bitwise identical** to ``gather_l2_blocked_raw``
    (same ``(C_BLK, d) -> (C_BLK,)`` f32 reduction shape) — pinned by
    tests/test_kernels.py, which is what lets the engine's cross-backend
    id-equality and the E=1 golden snapshot survive the backend swap.

Attribute rows are tiny (m ~ 3-5 floats), so the extra per-row DMA rides
in the shadow of the (d,)-row vector DMA; distances accumulate in f32
(bf16 corpora supported, attrs stay f32).

The in-kernel predicate doubles as the **tombstone lane** of the
streaming write path (DESIGN.md §11): a deleted row's attrs are NaN'd
in place, NaN fails every ``qlo <= a <= qhi`` comparison, and the lane
emits +inf — deletes thread through this kernel with zero kernel
changes and zero retraces (the index shapes are untouched).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .blocks import (col_block, gather_rows, lane_block, row_block,
                     tile_rows, tile_pad)

__all__ = ["gather_l2_filter_blocked_kernel", "gather_l2_filter_blocked_raw",
           "gather_l2_filter_q8_blocked_kernel",
           "gather_l2_filter_q8_blocked_raw"]


def _score(ids_ref, q, rows, arows_ref, qlo_ref, qhi_ref, o_ref) -> None:
    """``where(in_range & valid, sum((q-row)^2), +inf)`` for the tile."""
    d = q - rows
    dist = jnp.sum(d * d, axis=-1)                       # (c_blk,)
    a = arows_ref[...].astype(jnp.float32)               # (c_blk, m)
    ok = jnp.all((a >= qlo_ref[...]) & (a <= qhi_ref[...]), axis=-1)
    valid = ids_ref[...][:, 0] >= 0
    o_ref[...] = jnp.where(ok & valid, dist, jnp.inf)[None, :]


def gather_l2_filter_blocked_kernel(idx_ref, corpus_ref, ids_ref, arows_ref,
                                    q_ref, qlo_ref, qhi_ref, o_ref, tiles_ref,
                                    rows_ref, sems_ref):
    """Grid (B, C/C_BLK): step (i, j) gathers the vector rows of
    idx[i, j*C_BLK : (j+1)*C_BLK] via overlapping per-row tile DMAs, then
    emits ``where(in_range & valid, sum((q-row)^2), +inf)`` for the whole
    tile against the candidates' attribute rows."""
    gather_rows(idx_ref, corpus_ref, tiles_ref, rows_ref, sems_ref)
    _score(ids_ref, q_ref[...].astype(jnp.float32), rows_ref[...], arows_ref,
           qlo_ref, qhi_ref, o_ref)


def _filter_call(kernel, idx, corpus, narrow, q, qlo, qhi, c_blk,
                 interpret):
    """Shared launcher: the kernel gathers ``corpus`` rows tile-wise; the
    rows of each ``narrow`` (N, X) plane (attrs, the int8 scale) are
    gathered by XLA and ride as (B, C, X) blocks — an X < 128 plane sits
    lane-padded in its tiles, and Mosaic cannot slice an X-wide row out of
    one. Per-query operands ride as (B, 1, X) row blocks."""
    B, C = idx.shape
    D, M = corpus.shape[1], qlo.shape[1]
    c_blk = min(c_blk, C)
    pad = (-C) % c_blk
    if pad:
        idx = jnp.pad(idx, ((0, 0), (0, pad)), constant_values=-1)
    n_blk = (C + pad) // c_blk
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, n_blk),
            in_specs=([pl.BlockSpec(memory_space=pl.ANY),  # corpus tiles
                       col_block(c_blk)]                # ids (pad test)
                      + [col_block(c_blk, x.shape[1]) for x in narrow]
                      + [row_block(D), row_block(M), row_block(M)]),
            out_specs=lane_block(c_blk, lambda j: j),
            scratch_shapes=[
                pltpu.VMEM((c_blk, tile_rows(corpus.dtype), D), corpus.dtype),
                pltpu.VMEM((c_blk, D), jnp.float32),    # the picked rows
                pltpu.SemaphoreType.DMA((c_blk,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, 1, n_blk * c_blk), jnp.float32),
        interpret=interpret,
    )(idx, tile_pad(corpus), idx[:, :, None],
      *[x[jnp.maximum(idx, 0)] for x in narrow], q[:, None], qlo[:, None],
      qhi[:, None])
    return out[:, 0, :C]


def gather_l2_filter_blocked_raw(idx: jax.Array, corpus: jax.Array,
                                 attrs: jax.Array, q: jax.Array,
                                 qlo: jax.Array, qhi: jax.Array,
                                 *, c_blk: int = 128,
                                 interpret: bool = False) -> jax.Array:
    """idx (B, C) int32 (-1 = pad/invalid), corpus (N, d), attrs (N, m) f32,
    q (B, d), qlo/qhi (B, m) f32 -> (B, C) f32 with +inf on invalid or
    out-of-range lanes.

    Same tiling contract as ``gather_l2_blocked_raw`` (idx padded to a
    ``c_blk`` multiple — with -1 here, so pad lanes emit +inf and are
    sliced off); the corpus plane stays whole in compiler-chosen (HBM at
    size) memory and is DMA'd tile-wise into the scratch tiles, while the
    candidates' attrs rows are gathered by XLA (``_filter_call``)."""
    return _filter_call(gather_l2_filter_blocked_kernel, idx, corpus,
                        (attrs,), q, qlo, qhi, c_blk, interpret)


def gather_l2_filter_q8_blocked_kernel(idx_ref, corpus_ref, ids_ref,
                                       srows_ref, arows_ref, q_ref, qlo_ref,
                                       qhi_ref, o_ref, tiles_ref, rows_ref,
                                       sems_ref):
    """int8-replica variant of ``gather_l2_filter_blocked_kernel``
    (DESIGN.md §12): each candidate DMAs the tile holding its int8 vector
    row, which dequantizes in-kernel against its (1,) f32 scale
    (``rows.astype(f32) * scale`` — ``kernels.quant.dequant_rows``)."""
    gather_rows(idx_ref, corpus_ref, tiles_ref, rows_ref, sems_ref)
    rows = rows_ref[...] * srows_ref[...]
    _score(ids_ref, q_ref[...].astype(jnp.float32), rows, arows_ref,
           qlo_ref, qhi_ref, o_ref)


def gather_l2_filter_q8_blocked_raw(idx: jax.Array, qcorpus: jax.Array,
                                    qscale: jax.Array, attrs: jax.Array,
                                    q: jax.Array, qlo: jax.Array,
                                    qhi: jax.Array, *, c_blk: int = 128,
                                    interpret: bool = False) -> jax.Array:
    """idx (B, C) int32 (-1 = pad), qcorpus (N, d) int8 with per-row
    scale qscale (N, 1) f32, attrs (N, m) f32, q (B, d), qlo/qhi (B, m)
    -> (B, C) f32 quantized distances with +inf on invalid or
    out-of-range lanes. Same tiling contract as
    ``gather_l2_filter_blocked_raw``; oracle is
    ``ref.gather_l2_filter_q8_ref``."""
    return _filter_call(gather_l2_filter_q8_blocked_kernel, idx, qcorpus,
                        (qscale, attrs), q, qlo, qhi, c_blk, interpret)
