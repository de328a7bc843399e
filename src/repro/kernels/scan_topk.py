"""Predicate-fused brute-scan + streaming top-k Pallas kernel (DESIGN.md §10).

The planner's ``strategy="scan"`` path answers a query *exactly*: one pass
over the full corpus (or shard), masked squared L2 against the range
predicate, smallest-k survivors. Where the graph engine's kernels gather
*candidate* rows through a scalar-prefetched id stream
(``kernels.gather_l2_filter``), the scan visits **every** row — so the id
stream disappears and the corpus streams through VMEM block-sequentially
(grid ``(B, N/N_BLK)``, corpus/attrs blocks auto-pipelined by the
BlockSpec index_map), which is the shape HBM bandwidth likes best.

Per grid step the kernel

  1. reduces the ``(N_BLK, d)`` corpus tile against the query row —
     ``sum((q - row)^2)`` with the same per-row f32 reduction shape as
     the gather kernels (bitwise-equal distances on the same rows);
  2. evaluates ``all(qlo <= a <= qhi)`` on the ``(N_BLK, m)`` attrs tile
     in-kernel, exactly like ``gather_l2_filter`` — out-of-range lanes
     become +inf (NaN attrs — the caller's structural-padding mask —
     always fail the predicate);
  3. folds the tile into a **streaming top-k** carried in the revisited
     ``(1, k)`` output blocks: k argmin-extraction steps over the
     concatenated [running top-k | tile] distances. Extraction order is
     (distance, stream position) — and because blocks arrive in
     ascending row order and the running buffer keeps its entries
     (dist, id)-sorted, stream position IS row id order, so ties break
     to the lowest id: exactly ``lax.top_k`` semantics. Empty lanes are
     (-1, +inf).

The jnp oracle is ``kernels.ref.scan_topk_ref``; tests pin **bit
equality of the returned ids** against it — including all-out-of-range
and k > in-range-count workloads — plus the exact +inf empty-lane
pattern. Distances agree up to f32 reduce-order association (the
kernel reduces per ``(n_blk, d)`` tile, the oracle over the full
tensor; XLA may associate the two row sums differently by 1 ulp).
``c_blk``-style tiling notes: rows pad to an ``n_blk`` multiple with
NaN attrs (padded lanes can never win), distances accumulate in f32
(bf16 corpora supported, attrs stay f32).

The NaN-attrs mask is also the streaming write path's **tombstone and
delta lane** (DESIGN.md §11): deleted rows — epoch or delta — get NaN
attrs and drop out of every scan, and ``core.delta.DeltaSegment``
serves its append buffer through this same kernel (unwritten slots are
born NaN), so inserts/deletes need no kernel changes and no retraces.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .blocks import lane_block, row_block

__all__ = ["scan_topk_kernel", "scan_topk_raw",
           "scan_topk_q8_kernel", "scan_topk_q8_raw",
           "scan_topk_mask_kernel", "scan_topk_mask_raw",
           "scan_topk_windows_kernel", "scan_topk_windows_raw"]


def scan_topk_kernel(corpus_ref, attrs_ref, q_ref, qlo_ref, qhi_ref,
                     ids_ref, dists_ref):
    """Grid (B, N/N_BLK): step (i, j) scores corpus rows
    [j*N_BLK, (j+1)*N_BLK) against query i and merges them into the
    running (1, k) top-k carried in the revisited output blocks."""
    j = pl.program_id(1)
    n_blk = corpus_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        ids_ref[...] = jnp.full(ids_ref.shape, -1, jnp.int32)
        dists_ref[...] = jnp.full(dists_ref.shape, jnp.inf, jnp.float32)

    d = q_ref[...].astype(jnp.float32) - corpus_ref[...].astype(jnp.float32)
    dist = jnp.sum(d * d, axis=-1)                       # (n_blk,)
    a = attrs_ref[...].astype(jnp.float32)               # (n_blk, m)
    ok = jnp.all((a >= qlo_ref[...]) & (a <= qhi_ref[...]), axis=-1)
    rows = j * n_blk + jax.lax.broadcasted_iota(jnp.int32, (1, n_blk), 1)
    _fold_tile_topk(jnp.where(ok, dist, jnp.inf)[None, :], rows, ids_ref,
                    dists_ref)


def scan_topk_raw(corpus: jax.Array, attrs: jax.Array, q: jax.Array,
                  qlo: jax.Array, qhi: jax.Array, *, k: int,
                  n_blk: int = 512,
                  interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """corpus (N, d), attrs (N, m) f32, q (B, d), qlo/qhi (B, m) f32 ->
    (ids (B, k) int32, dists (B, k) f32), exact masked top-k ascending.

    Tiling contract: rows pad to an ``n_blk`` multiple — corpus with
    zeros, attrs with NaN, so padded lanes fail the predicate and can
    never enter the top-k (the module docstring's mask convention; the
    planner uses the same NaN trick for structurally padded index rows).
    Output lanes past the in-range count are (-1, +inf)."""
    B = q.shape[0]
    N, D = corpus.shape
    M = attrs.shape[1]
    if not 1 <= k <= N:
        raise ValueError(f"k must be in [1, N={N}], got {k}")
    n_blk = min(n_blk, N)
    pad = (-N) % n_blk
    if pad:
        corpus = jnp.pad(corpus, ((0, pad), (0, 0)))
        attrs = jnp.pad(attrs, ((0, pad), (0, 0)),
                        constant_values=jnp.nan)
    n_blocks = (N + pad) // n_blk
    ids, dists = pl.pallas_call(
        scan_topk_kernel,
        grid=(B, n_blocks),
        in_specs=[
            pl.BlockSpec((n_blk, D), lambda i, j: (j, 0)),   # corpus tile
            pl.BlockSpec((n_blk, M), lambda i, j: (j, 0)),   # attrs tile
            row_block(D),                                    # query row
            row_block(M),                                    # qlo row
            row_block(M),                                    # qhi row
        ],
        out_specs=[
            lane_block(k, lambda j: 0),                      # running ids
            lane_block(k, lambda j: 0),                      # running dists
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, k), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, k), jnp.float32),
        ],
        interpret=interpret,
    )(corpus, attrs, q[:, None], qlo[:, None], qhi[:, None])
    return ids[:, 0], dists[:, 0]


def _fold_tile_topk(tile_d, rows, ids_ref, dists_ref):
    """Fold one scored tile — (1, n) distances, +inf where the row fails
    the predicate, and its (1, n) row ids — into the running (1, k) top-k
    carried in the revisited output blocks (the step every scan kernel
    shares).
    Extraction order is (distance, stream position), so with tiles
    arriving in ascending row order ties break to the lowest id. Each of
    the k steps is a masked extraction — the minimum, the lowest lane that
    holds it, select-based writes — because Mosaic has no dynamic lane
    indexing (``argmin`` + ``x[0, pos]`` + ``.at[0, t].set``)."""
    k = ids_ref.shape[1]
    cand_d = jnp.concatenate([dists_ref[...], tile_d], axis=1)
    cand_i = jnp.concatenate([ids_ref[...], rows], axis=1)
    L = cand_d.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, L), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def take(t, carry):
        cd, od, oi = carry
        dmin = jnp.min(cd, axis=1, keepdims=True)                  # (1, 1)
        pos = jnp.min(jnp.where(cd == dmin, lane, L), axis=1,
                      keepdims=True)         # first min: lowest-id tie-break
        hit = lane == pos
        idv = jnp.max(jnp.where(hit, cand_i, -1), axis=1, keepdims=True)
        od = jnp.where(slot == t, dmin, od)
        oi = jnp.where(slot == t, jnp.where(jnp.isinf(dmin), -1, idv), oi)
        return jnp.where(hit, jnp.inf, cd), od, oi

    _, od, oi = jax.lax.fori_loop(
        0, k, take, (cand_d, dists_ref[...], ids_ref[...]))
    dists_ref[...] = od
    ids_ref[...] = oi


def scan_topk_q8_kernel(corpus_ref, scale_ref, attrs_ref, q_ref, qlo_ref,
                        qhi_ref, ids_ref, dists_ref):
    """int8-replica variant of ``scan_topk_kernel`` (DESIGN.md §12): the
    (N_BLK, d) int8 tile streams with its (N_BLK, 1) f32 scale plane and
    dequantizes in-kernel (``rows.astype(f32) * scale``), quartering the
    HBM bytes per scanned row."""
    j = pl.program_id(1)
    n_blk = corpus_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        ids_ref[...] = jnp.full(ids_ref.shape, -1, jnp.int32)
        dists_ref[...] = jnp.full(dists_ref.shape, jnp.inf, jnp.float32)

    rows_f = corpus_ref[...].astype(jnp.float32) * scale_ref[...]
    d = q_ref[...].astype(jnp.float32) - rows_f
    dist = jnp.sum(d * d, axis=-1)                       # (n_blk,)
    a = attrs_ref[...].astype(jnp.float32)               # (n_blk, m)
    ok = jnp.all((a >= qlo_ref[...]) & (a <= qhi_ref[...]), axis=-1)
    rows = j * n_blk + jax.lax.broadcasted_iota(jnp.int32, (1, n_blk), 1)
    _fold_tile_topk(jnp.where(ok, dist, jnp.inf)[None, :], rows, ids_ref,
                    dists_ref)


def scan_topk_q8_raw(qcorpus: jax.Array, qscale: jax.Array,
                     attrs: jax.Array, q: jax.Array, qlo: jax.Array,
                     qhi: jax.Array, *, k: int, n_blk: int = 512,
                     interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """qcorpus (N, d) int8 with per-row scale qscale (N, 1) f32, attrs
    (N, m) f32, q (B, d), qlo/qhi (B, m) -> (ids (B, k) int32, dists
    (B, k) f32): exact masked top-k of the *dequantized* distances
    (oracle ``ref.scan_topk_q8_ref``; the engine reranks through the f32
    path). Same NaN-attrs padding contract as ``scan_topk_raw``."""
    B = q.shape[0]
    N, D = qcorpus.shape
    M = attrs.shape[1]
    if not 1 <= k <= N:
        raise ValueError(f"k must be in [1, N={N}], got {k}")
    n_blk = min(n_blk, N)
    pad = (-N) % n_blk
    if pad:
        qcorpus = jnp.pad(qcorpus, ((0, pad), (0, 0)))
        qscale = jnp.pad(qscale, ((0, pad), (0, 0)), constant_values=1.0)
        attrs = jnp.pad(attrs, ((0, pad), (0, 0)),
                        constant_values=jnp.nan)
    n_blocks = (N + pad) // n_blk
    ids, dists = pl.pallas_call(
        scan_topk_q8_kernel,
        grid=(B, n_blocks),
        in_specs=[
            pl.BlockSpec((n_blk, D), lambda i, j: (j, 0)),   # int8 tile
            pl.BlockSpec((n_blk, 1), lambda i, j: (j, 0)),   # scale plane
            pl.BlockSpec((n_blk, M), lambda i, j: (j, 0)),   # attrs tile
            row_block(D),                                    # query row
            row_block(M),                                    # qlo row
            row_block(M),                                    # qhi row
        ],
        out_specs=[
            lane_block(k, lambda j: 0),                      # running ids
            lane_block(k, lambda j: 0),                      # running dists
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, k), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, k), jnp.float32),
        ],
        interpret=interpret,
    )(qcorpus, qscale, attrs, q[:, None], qlo[:, None], qhi[:, None])
    return ids[:, 0], dists[:, 0]


def scan_topk_mask_kernel(corpus_ref, mask_ref, q_ref, ids_ref, dists_ref):
    """Bitmask-fused variant of ``scan_topk_kernel`` (DESIGN.md §15): the
    in-kernel range test is replaced by a precomputed per-row mask plane —
    the predicate compiler's dense fallback for expressions whose disjoint
    box cover exceeds the budget. The (N_BLK, 1) f32 mask tile streams in
    place of the attrs tile (> 0 = row passes; padded rows ship 0), so
    arbitrary boolean structure costs the same HBM traffic as one attr."""
    j = pl.program_id(1)
    n_blk = corpus_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        ids_ref[...] = jnp.full(ids_ref.shape, -1, jnp.int32)
        dists_ref[...] = jnp.full(dists_ref.shape, jnp.inf, jnp.float32)

    d = q_ref[...].astype(jnp.float32) - corpus_ref[...].astype(jnp.float32)
    dist = jnp.sum(d * d, axis=-1)                       # (n_blk,)
    ok = mask_ref[...][:, 0] > 0.0                       # (n_blk,)
    rows = j * n_blk + jax.lax.broadcasted_iota(jnp.int32, (1, n_blk), 1)
    _fold_tile_topk(jnp.where(ok, dist, jnp.inf)[None, :], rows, ids_ref,
                    dists_ref)


def scan_topk_mask_raw(corpus: jax.Array, mask: jax.Array, q: jax.Array,
                       *, k: int, n_blk: int = 512,
                       interpret: bool = False
                       ) -> tuple[jax.Array, jax.Array]:
    """corpus (N, d), mask (N,) or (N, 1) f32 (> 0 = row passes), q (B, d)
    -> (ids (B, k) int32, dists (B, k) f32), exact masked top-k ascending
    with (-1, +inf) lanes past the pass count. Unlike the predicate-fused
    scans the mask is shared by every query in the batch (one compiled
    predicate, B queries). Rows pad with mask 0. Oracle:
    ``ref.scan_topk_mask_ref``."""
    B = q.shape[0]
    N, D = corpus.shape
    if not 1 <= k <= N:
        raise ValueError(f"k must be in [1, N={N}], got {k}")
    mask = mask.reshape(N, 1).astype(jnp.float32)
    n_blk = min(n_blk, N)
    pad = (-N) % n_blk
    if pad:
        corpus = jnp.pad(corpus, ((0, pad), (0, 0)))
        mask = jnp.pad(mask, ((0, pad), (0, 0)))
    n_blocks = (N + pad) // n_blk
    ids, dists = pl.pallas_call(
        scan_topk_mask_kernel,
        grid=(B, n_blocks),
        in_specs=[
            pl.BlockSpec((n_blk, D), lambda i, j: (j, 0)),   # corpus tile
            pl.BlockSpec((n_blk, 1), lambda i, j: (j, 0)),   # mask plane
            row_block(D),                                    # query row
        ],
        out_specs=[
            lane_block(k, lambda j: 0),                      # running ids
            lane_block(k, lambda j: 0),                      # running dists
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, k), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, k), jnp.float32),
        ],
        interpret=interpret,
    )(corpus, mask, q[:, None])
    return ids[:, 0], dists[:, 0]


def scan_topk_windows_kernel(starts_ref, counts_ref, corpus_ref, attrs_ref,
                             q_ref, qlo_ref, qhi_ref, ids_ref, dists_ref,
                             rows_ref, arows_ref, vsem_ref, asem_ref):
    """Grid (B, W): step (i, w) brute-scans the contiguous position
    window [starts[i, w], starts[i, w] + counts[i, w]) of a
    position-ordered corpus and folds it into query i's running (1, k)
    top-k (DESIGN.md §12 — the hybrid planner's per-node scan).

    The window slice DMAs as ONE contiguous block of whole tiles covering
    it (plus its attrs block) — the sequential-stream shape HBM likes —
    with lanes outside the window masked out; pad windows (start = -1)
    carry count 0, so every lane masks and the DMA (clamped to row 0) is
    harmless. Emitted ids are POSITIONS; the caller maps them back
    through the DFS ``order`` permutation."""
    i = pl.program_id(0)
    w = pl.program_id(1)
    w_tot = rows_ref.shape[0]

    @pl.when(w == 0)
    def _init():
        ids_ref[...] = jnp.full(ids_ref.shape, -1, jnp.int32)
        dists_ref[...] = jnp.full(dists_ref.shape, jnp.inf, jnp.float32)

    s = jnp.maximum(starts_ref[i, w], 0)
    cnt = counts_ref[i, w]
    # DMA whole tiles from a 128-aligned base (the attrs plane rides
    # transposed, lane-dense, so its window is a lane slice); the buffers
    # are sized for the worst offset and lanes outside [s, s + cnt) mask
    base = pl.multiple_of(s // 128 * 128, 128)
    vdma = pltpu.make_async_copy(corpus_ref.at[pl.ds(base, w_tot)],
                                 rows_ref, vsem_ref)
    adma = pltpu.make_async_copy(attrs_ref.at[:, pl.ds(base, w_tot)],
                                 arows_ref, asem_ref)
    vdma.start()
    adma.start()
    vdma.wait()
    adma.wait()

    d = q_ref[...].astype(jnp.float32) - rows_ref[...].astype(jnp.float32)
    dist = jnp.sum(d * d, axis=-1)                       # (w_tot,)
    a = arows_ref[...].astype(jnp.float32)               # (m, w_tot)
    pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, w_tot), 1)
    ok = (jnp.all((a >= qlo_ref[...]) & (a <= qhi_ref[...]), axis=0,
                  keepdims=True)
          & (pos >= s) & (pos < s + cnt))
    _fold_tile_topk(jnp.where(ok, dist[None, :], jnp.inf), pos, ids_ref,
                    dists_ref)


def _col(m: int) -> pl.BlockSpec:
    """Query i's (m, 1) column of a (B, m, 1) operand."""
    return pl.BlockSpec((None, m, 1), lambda i, *_: (i, 0, 0))


def scan_topk_windows_raw(corpus: jax.Array, attrs: jax.Array,
                          q: jax.Array, qlo: jax.Array, qhi: jax.Array,
                          starts: jax.Array, counts: jax.Array, *, k: int,
                          w_cap: int,
                          interpret: bool = False
                          ) -> tuple[jax.Array, jax.Array]:
    """corpus (N, d) / attrs (N, m) in POSITION order, q (B, d), qlo/qhi
    (B, m), starts/counts (B, W) int32 antichain windows (disjoint;
    start = -1 pads; every count <= w_cap) -> (ids (B, k) int32 positions,
    dists (B, k) f32), exact masked top-k over the union of each query's
    windows. Oracle: ``ref.scan_topk_windows_ref``.

    Bit-parity tie-break contract: windows must arrive sorted ascending
    by start per lane (the planner sorts), so stream position order ==
    global position order and ties break to the lowest position exactly
    like ``lax.top_k``. The corpus pads with NaN-attr rows so a window
    starting near N can DMA all its tiles without running off the
    buffer."""
    B = q.shape[0]
    N, D = corpus.shape
    M = attrs.shape[1]
    W = starts.shape[1]
    if w_cap < 1:
        raise ValueError(f"w_cap must be >= 1, got {w_cap}")
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    w_tot = -(-(w_cap + 127) // 128) * 128   # aligned span of any window
    corpus = jnp.pad(corpus, ((0, w_tot), (0, 0)))
    attrs = jnp.pad(attrs, ((0, w_tot), (0, 0)), constant_values=jnp.nan)
    ids, dists = pl.pallas_call(
        scan_topk_windows_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, W),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),    # corpus (windows DMA)
                pl.BlockSpec(memory_space=pl.ANY),    # attrs  (windows DMA)
                row_block(D),
                _col(M), _col(M),                       # qlo / qhi columns
            ],
            out_specs=[
                lane_block(k, lambda w: 0),             # running ids
                lane_block(k, lambda w: 0),             # running dists
            ],
            scratch_shapes=[
                pltpu.VMEM((w_tot, D), corpus.dtype),
                pltpu.VMEM((M, w_tot), attrs.dtype),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, k), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, k), jnp.float32),
        ],
        interpret=interpret,
    )(starts, counts, corpus, attrs.T, q[:, None], qlo[:, :, None],
      qhi[:, :, None])
    return ids[:, 0], dists[:, 0]
