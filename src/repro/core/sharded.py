"""Corpus-sharded distributed KHI search (DESIGN.md §2 "Distribution", §14).

Industry-standard fan-out design (Milvus/Vespa): the `model` mesh axis holds
S independent KHI shards, each built over n/S objects; queries are replicated
across `model`, data-parallel across (`pod` x) `data`. Each shard answers
top-k locally; a cross-shard merge-k produces the global answer. Two merge
forms share one (dist, id) lexicographic contract (DESIGN.md §14):

  * ``allgather`` — one all_gather + top-k over (S, k): O(S·k) bytes per
    device per query, the classic fan-in.
  * ``halving`` — recursive-halving pairwise merge over `model`
    (log2 S ``ppermute`` rounds, partner = rank XOR 2^r), O(k·log S)
    bytes per device; bit-identical to the allgather form because each
    entry carries its flat (shard·k + rank) tie key.

Per-shard index arrays are padded to common shapes and stacked on a leading
shard axis, so the whole sharded index is ONE pytree whose leaves are sharded
on axis 0 over `model` — `jax.jit` in/out shardings handle the rest.
``ShardedKHI.pad_waste`` records what the padding costs.

Fault tolerance: every shard is an independent artifact ((shard_id, epoch)
keyed .npz). A lost host reloads only its shard; `elastic_reshard` (see
repro.distributed.elastic) re-partitions object ids and rebuilds only moved
shards; ``stack_shards`` re-stacks the result for the collective program.

Every engine-side knob — the wide-frontier ``expand_width``, the scoring
``backend`` (Scorer registry, DESIGN.md §9) and the Phase-A ``router``
(level-sync sweep or legacy DFS) — rides in ``SearchParams`` unchanged:
each shard runs the same two-phase ``_query_one`` program the
single-device engine runs.

Strategy dispatch (``SearchParams.strategy``, DESIGN.md §10) is collective
(DESIGN.md §14): ``make_sharded_search_fn`` lowers every strategy —
graph, scan, auto, hybrid, any quant tier — through one jitted shard_map
program. "auto" runs the ``route_level_card`` sweep per shard inside the
collective and ``psum``s the per-shard bounds over `model`, so every
member of a model group takes the same branch per lane with no host
round-trip; "hybrid" does the same with ``route_level_windows``.
``search_sharded_emulated`` remains the single-device semantic reference
(vmap fan-out + host ``engine.Planner`` dispatch) the collective is
pinned bit-identical to.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .engine import (DEFAULT_SCAN_FRAC, DeviceIndex, SearchParams,
                     _merge_dedup_jnp, _query_one, _scan_shard_topk,
                     _windows_one, device_put_index, resolve_scorer,
                     resolve_scorer_pair, validate_search_params,
                     with_quant_replica)
from .khi import KHIConfig, KHIIndex
from .router import route_level_card, route_level_windows
from .util import pow2_at_least

__all__ = ["ShardedKHI", "build_sharded", "stack_shards",
           "make_sharded_search_fn", "merge_bytes_per_device",
           "sharded_input_specs", "search_sharded_emulated"]

logger = logging.getLogger(__name__)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShardedKHI:
    """Stacked per-shard DeviceIndex (leading axis = shard) + global offsets.

    ``pad_waste`` is static metadata (pytree aux, hashable): the fraction
    of stacked array slots that are padding, per plane — ``(rows, nodes,
    levels)``. Round-robin partitioning keeps every term < 1/S + ε
    (pinned by tests); a skewed external partition shows up here before
    it shows up in the device-memory bill."""

    di: DeviceIndex          # every leaf has leading dim S
    offsets: jax.Array       # (S,) int32 global-id base per shard
    pad_waste: tuple = ()    # static: (row_frac, node_frac, level_frac)

    def tree_flatten(self):
        return (self.di, self.offsets), self.pad_waste

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, pad_waste=aux if aux is not None else ())

    @property
    def num_shards(self) -> int:
        return self.offsets.shape[0]


def stack_shards(shards: Sequence[KHIIndex]) -> ShardedKHI:
    """Pad per-shard indexes to common shapes and stack them into one
    ShardedKHI (shard s holds the objects with global id ≡ s mod S —
    the round-robin contract ``_local_to_global`` inverts). This is the
    publish half of ``build_sharded``, split out so ``elastic_reshard``
    (repro.distributed.elastic) can re-stack a partially-rebuilt shard
    map without rebuilding the unmoved shards."""
    S = len(shards)
    max_n = max(ix.n for ix in shards)
    max_p = max(ix.tree.num_nodes for ix in shards)
    max_h = max(ix.height for ix in shards)
    dis = [device_put_index(ix, pad_n=max_n, pad_nodes=max_p,
                            pad_height=max_h)
           for ix in shards]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *dis)
    rows = dis[0].n                  # max_n padded to whole device tiles
    waste = (
        1.0 - sum(ix.n for ix in shards) / (S * rows),
        1.0 - sum(ix.tree.num_nodes for ix in shards) / (S * max_p),
        1.0 - sum(ix.height for ix in shards) / (S * max_h),
    )
    if max(waste) > 0:
        logger.info("stack_shards: pad waste rows=%.4f nodes=%.4f "
                    "levels=%.4f (S=%d, rows=%d)", *waste, S, rows)
    offsets = jnp.arange(S, dtype=jnp.int32)
    return ShardedKHI(di=stacked, offsets=offsets, pad_waste=waste)


def build_sharded(vecs: np.ndarray, attrs: np.ndarray, n_shards: int,
                  config: Optional[KHIConfig] = None) -> ShardedKHI:
    """Round-robin partition + per-shard build + pad&stack.

    Defaults to the jitted device builder (``KHIConfig(builder="device")``):
    shards share the builder's per-size-class traces, so S-shard builds pay
    one compile and S executions — the sharded-corpus regime the device
    path is designed for (DESIGN.md §7). Pass an explicit config for the
    numpy builders."""
    config = config or KHIConfig(builder="device")
    n = vecs.shape[0]
    shard_of = np.arange(n) % n_shards
    locals_ = []
    for s in range(n_shards):
        ids = np.nonzero(shard_of == s)[0]
        locals_.append(KHIIndex.build(vecs[ids], attrs[ids], config))
    return stack_shards(locals_)


def _local_to_global(local_ids: jax.Array, shard: jax.Array,
                     n_shards: int) -> jax.Array:
    """Round-robin inverse: global = local * S + shard (keeps -1 invalid)."""
    return jnp.where(local_ids >= 0, local_ids * n_shards + shard, -1)


def _shard_search(di: DeviceIndex, shard_id: jax.Array, n_shards: int,
                  queries, qlo, qhi, p: SearchParams, scorer,
                  exact_scorer=None):
    """One shard's vmapped graph search -> (global ids (B, k), dists
    (B, k), hops (B,), engine.LaneCounts of the B lanes)."""
    fn = functools.partial(_query_one, p=p, scorer=scorer,
                           exact_scorer=exact_scorer)
    ids, dists, hops, counts = jax.vmap(lambda q, lo, hi: fn(di, q, lo, hi))(
        queries, qlo, qhi)
    gids = _local_to_global(ids, shard_id, n_shards)
    dists = jnp.where(gids >= 0, dists, jnp.inf)
    return gids, dists, hops, counts


def _merge_topk(gids, dists, k):
    """gids/dists (S, B, k) -> global (B, k) by merge-k."""
    S, B, kk = gids.shape
    flat_i = jnp.transpose(gids, (1, 0, 2)).reshape(B, S * kk)
    flat_d = jnp.transpose(dists, (1, 0, 2)).reshape(B, S * kk)
    neg, sel = jax.lax.top_k(-flat_d, k)
    return jnp.take_along_axis(flat_i, sel, axis=1), -neg


def _pair_merge_k(ids, d, tie, oids, od, otie, k: int):
    """Merge two (B, k) top-k lists into the k best by the (dist, tie)
    lexicographic key — one round of the halving merge (DESIGN.md §14).
    The tie key is each entry's flat position shard·k + rank in the
    conceptual (S·k,) gathered list, so the winner set AND its order are
    exactly ``_merge_topk``'s (lax.top_k breaks distance ties to the
    lowest flat index)."""
    cd = jnp.concatenate([d, od], axis=1)
    ci = jnp.concatenate([ids, oids], axis=1)
    ct = jnp.concatenate([tie, otie], axis=1)
    sel = jnp.lexsort((ct, cd), axis=-1)[:, :k]
    return (jnp.take_along_axis(ci, sel, axis=1),
            jnp.take_along_axis(cd, sel, axis=1),
            jnp.take_along_axis(ct, sel, axis=1))


def _merge_topk_halving(gids, dists, k: int, axis_name: str, n_shards: int):
    """Collective twin of ``_merge_topk``: recursive-halving pairwise
    merge over ``axis_name`` (partner = rank XOR 2^r, log2 S ppermute
    rounds). Each device sends/receives k·(id, dist, tie) per round —
    O(k·log S) bytes instead of the all_gather's O(S·k) — and every
    device finishes with the identical replicated (B, k) answer, in
    ``_merge_topk``'s exact output order (see ``_pair_merge_k``).
    Requires S a power of two (the caller falls back to all_gather
    otherwise)."""
    r = jax.lax.axis_index(axis_name)
    tie = r * k + jnp.arange(k, dtype=jnp.int32)
    t = jnp.broadcast_to(tie[None, :], gids.shape)
    ids, d = gids, dists
    for rnd in range(n_shards.bit_length() - 1):
        bit = 1 << rnd
        perm = [(i, i ^ bit) for i in range(n_shards)]
        oids = jax.lax.ppermute(ids, axis_name, perm)
        od = jax.lax.ppermute(d, axis_name, perm)
        ot = jax.lax.ppermute(t, axis_name, perm)
        ids, d, t = _pair_merge_k(ids, d, t, oids, od, ot, k)
    return ids, d


def merge_bytes_per_device(k: int, n_shards: int, merge: str) -> int:
    """Bytes each device moves per query batch row for the cross-shard
    merge (DESIGN.md §14's accounting): the all_gather form receives
    (S-1)·k (id, dist) entries at 8 bytes; the halving form exchanges
    log2(S)·k (id, dist, tie) entries at 12 bytes. The two tie at S = 4;
    the log2 S vs S-1 asymptotics dominate the 12/8 constant beyond."""
    if n_shards <= 1:
        return 0
    if merge == "halving":
        return 12 * k * (n_shards.bit_length() - 1)
    return 8 * k * (n_shards - 1)


def _resolve_merge(merge: str, n_shards: int) -> str:
    if merge not in ("auto", "halving", "allgather"):
        raise ValueError(f"merge={merge!r}: expected auto|halving|allgather")
    pow2 = n_shards >= 2 and (n_shards & (n_shards - 1)) == 0
    if merge == "halving" and not pow2:
        raise ValueError(
            f"merge='halving' needs a power-of-two model axis >= 2, got "
            f"S={n_shards}; use merge='auto' to fall back to all_gather")
    if merge == "auto":
        return "halving" if pow2 else "allgather"
    return merge


def make_sharded_search_fn(params: SearchParams, mesh: Mesh, *,
                           model_axis: str = "model",
                           data_axes: Sequence[str] = ("data",),
                           dist_fn=None, skhi: Optional[ShardedKHI] = None,
                           on_undersized: str = "raise",
                           merge: str = "auto", interpret=None):
    """Returns jit(search)(skhi, queries, qlo, qhi) -> (ids, dists) with the
    production sharding: index on `model`, batch on data axes, and the whole
    per-query pipeline — planner dispatch included — inside one collective
    shard_map program (DESIGN.md §14).

    Every strategy lowers: "graph" and "scan" run their pass on all lanes;
    "auto" runs the ``route_level_card`` sweep per shard in-collective,
    ``psum``s the per-shard bounds over `model`, and branches each lane
    device-side by masking the losing pass's range box to the empty box
    (lo=+inf > hi=-inf — the graph walk exits its hop loop immediately and
    a scan lane matches no rows); "hybrid" routes with
    ``route_level_windows`` and merges its graph and window streams with
    the device ``_merge_dedup_jnp``. Whole passes are gated by ``lax.cond``
    on batch-level predicates that are uniform across the model group
    (they derive from psum'ed quantities), so a pure-scan batch never pays
    the graph walk and vice versa. Cross-shard merges use the O(k·log S)
    recursive-halving form when S is a power of two (``merge=``,
    bit-identical to ``_merge_topk`` — module docstring).

    "auto" needs a dispatch threshold and "hybrid" additionally needs the
    static window bounds — both derive from per-shard corpus counts, so
    those strategies require ``skhi=`` (or, for "auto", an explicit
    ``SearchParams.scan_threshold``). Passing ``skhi`` also validates the
    index-dependent buffer bounds up front (see
    ``engine.validate_search_params``); dry-run callers lower the graph
    program against ShapeDtypeStructs and skip it."""
    n_shards = mesh.shape[model_axis]
    merge = _resolve_merge(merge, n_shards)
    if skhi is not None:
        if skhi.num_shards != n_shards:
            raise ValueError(
                f"skhi has {skhi.num_shards} shards but mesh axis "
                f"{model_axis!r} has {n_shards}")
        params = validate_search_params(params, skhi.di,
                                        on_undersized=on_undersized)
        if params.quant != "none" and skhi.di.qvecs is None:
            raise ValueError(
                f"quant={params.quant!r} needs the quantized replica on the "
                f"sharded index the collective fn will be called with — "
                f"attach it up front: skhi = dataclasses.replace(skhi, "
                f"di=with_quant_replica(skhi.di, {params.quant!r}))")
    p = params
    strategy = p.strategy

    # ---- static planner state (DESIGN.md §14): the dispatch threshold and
    # the hybrid window bounds are index-DERIVED but shape-static, resolved
    # once here so the collective body stays a fixed program.
    scan_threshold = node_thr = 0
    W = w_cap = 1
    if strategy in ("auto", "hybrid"):
        if skhi is not None:
            root = np.atleast_1d(np.asarray(jax.device_get(skhi.di.root)))
            count = np.atleast_2d(np.asarray(jax.device_get(skhi.di.count)))
            n_total = int(count[np.arange(root.shape[0]), root].sum())
            scan_threshold = int(p.scan_threshold) or max(
                1, int(DEFAULT_SCAN_FRAC * n_total))
        elif strategy == "auto" and int(p.scan_threshold) > 0:
            scan_threshold = int(p.scan_threshold)
        else:
            raise ValueError(
                f"strategy={strategy!r} under the collective needs the "
                f"dispatch threshold{' and window bounds' if strategy == 'hybrid' else ''}"
                f", which derive from per-shard corpus counts — pass skhi="
                f"{' or set SearchParams.scan_threshold' if strategy == 'auto' else ''}"
                f" (DESIGN.md §14)")
    if strategy == "hybrid":
        node_thr = int(p.node_scan_threshold) or scan_threshold
        count = np.atleast_2d(np.asarray(jax.device_get(skhi.di.count)))
        small = (count > 0) & (count <= node_thr)
        # W bounds the per-query small-antichain size per shard: at most
        # every statically-small node, at most frontier_cap per level
        H = skhi.di.nbrs.shape[-2]
        max_small = int(small.sum(axis=1).max())
        W = pow2_at_least(max(1, min(max_small, p.frontier_cap * H)))
        w_cap = pow2_at_least(max(1, int(count[small].max(initial=1))))

    scorer, exact = resolve_scorer_pair(p, dist_fn=dist_fn,
                                        interpret=interpret)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    use_kernel = p.backend == "pallas_gather_l2_filter"
    dspec = P(tuple(data_axes))
    EMPTY = (jnp.float32(jnp.inf), jnp.float32(-jnp.inf))

    def merge_k(gids, dists):
        if merge == "halving":
            return _merge_topk_halving(gids, dists, p.k, model_axis,
                                       n_shards)
        allg = jax.lax.all_gather(gids, model_axis)    # (S, B, k)
        alld = jax.lax.all_gather(dists, model_axis)
        return _merge_topk(allg, alld, p.k)

    def empty_topk(B):
        return (jnp.full((B, p.k), -1, jnp.int32),
                jnp.full((B, p.k), jnp.inf, jnp.float32))

    def local(di_blk, off_blk, queries, qlo, qhi):
        di = jax.tree.map(lambda x: x[0], di_blk)      # squeeze shard axis
        shard_id = off_blk[0]
        B = queries.shape[0]

        def graph_pass(lo, hi):
            gids, dists, _, _ = _shard_search(di, shard_id, n_shards,
                                              queries, lo, hi, p, scorer,
                                              exact_scorer=exact)
            return gids, dists

        if strategy == "graph":
            return merge_k(*graph_pass(qlo, qhi))

        # scan paths NaN-mask structurally padded rows in-collective —
        # the same mask the Planner precomputes host-side (DESIGN.md §10)
        n_real = di.count[di.root]
        valid = jnp.arange(di.attrs.shape[0]) < n_real
        attrs_nan = jnp.where(valid[:, None], di.attrs, jnp.nan)

        def scan_pass(lo, hi):
            ids, dd = _scan_shard_topk(di, None, attrs_nan, queries, lo, hi,
                                       p, use_kernel=use_kernel,
                                       interpret=interpret)
            gids = _local_to_global(ids, shard_id, n_shards)
            return gids, jnp.where(gids >= 0, dd, jnp.inf)

        if strategy == "scan":
            return merge_k(*scan_pass(qlo, qhi))

        def mask_box(keep):
            lo = jnp.where(keep[:, None], qlo, EMPTY[0])
            hi = jnp.where(keep[:, None], qhi, EMPTY[1])
            return lo, hi

        if strategy == "auto":
            card = jax.vmap(
                lambda lo, hi: route_level_card(di, lo, hi, p))(qlo, qhi)
            card = jax.lax.psum(card, model_axis)
            use_scan = (card > 0) & (card <= scan_threshold)
            # batch-level gates are uniform across the model group (card
            # is psum'ed) — collectives stay OUTSIDE the conds
            g_ids, g_d = jax.lax.cond(
                jnp.any(~use_scan),
                lambda: graph_pass(*mask_box(~use_scan)),
                lambda: empty_topk(B))
            s_ids, s_d = jax.lax.cond(
                jnp.any(use_scan),
                lambda: scan_pass(*mask_box(use_scan)),
                lambda: empty_topk(B))
            ids = jnp.where(use_scan[:, None], s_ids, g_ids)
            d = jnp.where(use_scan[:, None], s_d, g_d)
            return merge_k(ids, d)

        # ---- hybrid (DESIGN.md §12 semantics, §14 execution): per-NODE
        # split of each lane's antichain into large (graph) and small
        # (windowed exact scan) nodes, routed device-side
        card, n_small, n_large, wstarts, wcounts = jax.vmap(
            lambda lo, hi: route_level_windows(di, lo, hi, p,
                                               node_thr=node_thr, W=W)
        )(qlo, qhi)
        card = jax.lax.psum(card, model_axis)
        t_small = jax.lax.psum(n_small, model_axis)
        t_large = jax.lax.psum(n_large, model_axis)
        mode1 = (t_large == 0) & (card > 0)            # pure-window: exact
        mode2 = (t_large > 0) & (t_small > 0)          # mixed
        # collectives must stay OUTSIDE the lax.conds: the gates are
        # uniform within a model group but not across data groups, and a
        # data group skipping a ppermute/all_gather other groups run
        # deadlocks the CPU backend's all-device rendezvous — only the
        # local pass is gated, the merges always run (merging the empty
        # (B, k) fills is O(k) noise)
        g_ids, g_d = jax.lax.cond(
            jnp.any(~mode1),
            lambda: graph_pass(*mask_box(~mode1)),
            lambda: empty_topk(B))
        g_ids, g_d = merge_k(g_ids, g_d)
        order = di.order[:, None]
        pos_vecs = jnp.take_along_axis(di.vecs, order, axis=-2)
        pos_attrs = jnp.take_along_axis(attrs_nan, order, axis=-2)

        def windows_pass():
            ids, dd = _windows_one(pos_vecs, pos_attrs, di.order, queries,
                                   qlo, qhi, wstarts, wcounts, k=p.k,
                                   w_cap=w_cap, use_kernel=use_kernel,
                                   interpret=interpret)
            gids = _local_to_global(ids, shard_id, n_shards)
            return gids, jnp.where(gids >= 0, dd, jnp.inf)

        w_ids, w_d = jax.lax.cond(jnp.any(t_small > 0), windows_pass,
                                  lambda: empty_topk(B))
        w_ids, w_d = merge_k(w_ids, w_d)
        m_ids, m_d = _merge_dedup_jnp(g_ids, g_d, w_ids, w_d, p.k)
        ids = jnp.where(mode1[:, None], w_ids,
                        jnp.where(mode2[:, None], m_ids, g_ids))
        d = jnp.where(mode1[:, None], w_d,
                      jnp.where(mode2[:, None], m_d, g_d))
        return ids, d

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(model_axis), P(model_axis), dspec, dspec, dspec),
        out_specs=(dspec, dspec),
        check_vma=False,
    )
    return jax.jit(lambda skhi, q, qlo, qhi: fn(skhi.di, skhi.offsets, q, qlo, qhi))


def search_sharded_emulated(skhi: ShardedKHI, queries, qlo, qhi,
                            params: SearchParams, *, dist_fn=None,
                            on_undersized: str = "adjust", interpret=None):
    """Single-device semantic equivalent of the shard_map program (vmap over
    the shard axis instead of devices) — used by tests on this 1-CPU box.
    Index-dependent buffer bounds are auto-raised by default.

    ``params.strategy != "graph"`` delegates to an ``engine.Planner``
    (DESIGN.md §10); on that path ``hops`` comes back per query (B,) —
    max over shards for graph lanes, 0 for scan lanes — instead of the
    graph-only (S, B) per-shard array. The collective form
    (``make_sharded_search_fn``) is pinned bit-identical to this
    function on every strategy and quant tier (DESIGN.md §14)."""
    if params.strategy != "graph":
        from .engine import Planner
        planner = Planner(skhi, params, dist_fn=dist_fn,
                          on_undersized=on_undersized, interpret=interpret)
        ids, dists, hops, _ = planner.search(np.asarray(queries),
                                             np.asarray(qlo),
                                             np.asarray(qhi))
        return ids, dists, hops
    params = validate_search_params(params, skhi.di,
                                    on_undersized=on_undersized)
    if params.quant != "none" and skhi.di.qvecs is None:
        skhi = dataclasses.replace(
            skhi, di=with_quant_replica(skhi.di, params.quant))
    scorer, exact = resolve_scorer_pair(params, dist_fn=dist_fn,
                                        interpret=interpret)
    n_shards = skhi.num_shards

    @jax.jit
    def run(skhi, queries, qlo, qhi):
        def per_shard(di, off):
            return _shard_search(di, off, n_shards, queries, qlo, qhi,
                                 params, scorer, exact_scorer=exact)
        gids, dists, hops, _ = jax.vmap(per_shard)(skhi.di, skhi.offsets)
        mi, md = _merge_topk(gids, dists, params.k)
        return mi, md, hops

    return run(skhi, jnp.asarray(queries), jnp.asarray(qlo), jnp.asarray(qhi))


def sharded_input_specs(*, n_per_shard: int, d: int, m: int, height: int,
                        nodes_per_shard: int, M: int, n_shards: int,
                        batch: int, vec_dtype=None, quant: str = "none"):
    """ShapeDtypeStruct stand-ins for the dry-run (no allocation).

    ``quant`` mirrors ``with_quant_replica``'s trailing replica fields
    (DESIGN.md §12): "bf16" adds a (S, n, d) bf16 ``qvecs`` plane;
    "int8" adds (S, n, d) int8 ``qvecs`` plus the (S, n, 1) f32
    ``qscale`` plane — without them a quantized collective program
    cannot lower against specs."""
    f32, i32 = jnp.float32, jnp.int32
    vd = vec_dtype or f32

    def sd(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    S, n, Pn = n_shards, n_per_shard, nodes_per_shard
    if quant not in ("none", "bf16", "int8"):
        raise ValueError(f"unknown quant {quant!r}; expected none|bf16|int8")
    qvecs = qscale = None
    if quant == "bf16":
        qvecs = sd((S, n, d), jnp.bfloat16)
    elif quant == "int8":
        qvecs = sd((S, n, d), jnp.int8)
        qscale = sd((S, n, 1), f32)
    di = DeviceIndex(
        vecs=sd((S, n, d), vd), attrs=sd((S, n, m), f32),
        nbrs=sd((S, n, height, M), i32),
        left=sd((S, Pn), i32), right=sd((S, Pn), i32), dim=sd((S, Pn), i32),
        bl=sd((S, Pn), i32), lo=sd((S, Pn, m), f32), hi=sd((S, Pn, m), f32),
        start=sd((S, Pn), i32), count=sd((S, Pn), i32), order=sd((S, n), i32),
        root=sd((S,), i32),
        qvecs=qvecs, qscale=qscale,
    )
    skhi = ShardedKHI(di=di, offsets=sd((S,), i32))
    return skhi, {
        "queries": sd((batch, d), f32),
        "qlo": sd((batch, m), f32),
        "qhi": sd((batch, m), f32),
    }
