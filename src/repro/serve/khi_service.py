"""Batched multi-shard RFANNS serving layer (DESIGN.md §3 "Serving").

The paper's headline number is query *throughput*; this module is the
request-facing layer that turns the jitted engine into a service:

  * **Shape-bucket micro-batching** — incoming (query, range) requests are
    grouped and padded to the nearest batch bucket (default 1/8/32/128), so
    the number of distinct jit traces is bounded by ``len(buckets)`` no
    matter what batch sizes clients send. Pad lanes carry an *empty* range
    (lo=+inf, hi=-inf): RangeFilter returns zero entries and the greedy
    loop exits on its first condition check, so padding costs one masked
    lane, not a full search.
  * **Multi-shard fan-out** — a ``ShardedKHI`` is searched with the same
    program ``core.sharded`` distributes under shard_map: every shard
    answers top-k locally, one O(S·k) merge produces the global answer. On
    a multi-device mesh pass ``mesh=`` to get the collective form; without
    one the fan-out vmaps over the stacked shard axis (bit-identical
    semantics, single device).
  * **LRU result cache** — keyed on (query bytes, range bytes, k, backend,
    epoch); repeated requests (RAG loops, dashboard refreshes) skip the
    device entirely and return identical ids/dists.
  * **Epoch hot-swap** — ``swap_index`` atomically replaces the live
    (sharded) index with a freshly (re)built one without dropping queued
    requests; every swap bumps the epoch, which invalidates the result
    cache (DESIGN.md §7 "Epoch swap protocol").

The scoring backend (``"jnp" | "pallas_l2" | "pallas_gather_l2" |
"pallas_gather_l2_filter"``) comes from ``SearchParams.backend`` via the
Scorer registry (DESIGN.md §9) — the predicate-fused gather+filter+L2
kernel is selected the same way here as in offline search — and so do
the Phase-A ``router`` (level-sync sweep by default) and the
wide-frontier width (``SearchParams.expand_width``, DESIGN.md §8): E > 1
cuts the lockstep hop count of every micro-batch ~E-fold, which is worth
the most exactly here, where a bucket pads heterogeneous requests into one
vmapped program that runs to the slowest lane. All knobs are part of the
result-cache key (the key hashes ``repr(params)``).

``SearchParams.strategy`` selects the execution strategy (DESIGN.md §10):
``"auto"`` — the khi-serve production default — routes every micro-batch
through an ``engine.Planner`` that estimates each lane's in-range
cardinality from the routing sweep and dispatches it to the graph engine
or the exact brute-scan kernel; low-selectivity lanes get exact recall,
high-selectivity lanes keep graph QPS. Bucket pad lanes carry an empty
range, whose cardinality bound is 0 — the planner sends them to the
graph program, which exits immediately (a scan lane would pay a full
corpus pass). ``snapshot()["scan_lanes"]`` counts scan-dispatched lanes.
The Planner is host-side on the mesh-less path; with a ``mesh=`` every
strategy and quant tier lowers through the one collective shard_map
program of ``make_sharded_search_fn`` — the dispatch runs in-collective
off psum'ed routing bounds (DESIGN.md §14), so ``scan_lanes`` is not
tracked there (the decision never surfaces to the host).

**Compiled predicates** (DESIGN.md §15): ``search_expr`` (and ``Request
(expr=...)`` through flush/serve_stream) accepts a boolean filter
expression instead of one [lo, hi] box. The predicate compiler lowers it
to a union of DISJOINT conjunctive boxes; each box is served through the
normal ``_answer`` path — so per-box requests get the result cache, the
bucket padding and the streaming delta merge for free — and the
per-disjunct top-k streams merge under the ``_merge_dedup``
best-dist-per-id contract (sound because the cover is disjoint: dedup
only ever collapses pad lanes). Covers past ``SearchParams.box_budget``
fall back to the dense bitmask program, executed by a lazily-built
per-tier Planner (exact f32 scan; rejected under streaming — the host
mask plane cannot see delta rows — and on a mesh, where predicates do
not lower collectively yet; both raise actionable errors).
``snapshot()["predicate_lanes"]`` counts the (query × disjunct) device
lanes a compiled predicate dispatched per execution strategy
(graph/scan/window/bitmask; bucket-pad lanes count as graph — their
empty box is a cardinality-0 graph exit) — the host-path answer to PR-9's
"scan_lanes is not tracked under mesh" observability gap.

**Degradation tiers** (DESIGN.md §13): the service can carry a ladder of
``SearchParams`` variants (``tiers=`` / ``set_tiers``), and every entry
point takes ``tier=`` — tier 0 is the full-quality default, higher tiers
are cheaper (lower ``ef``/``expand_width``, shifted planner thresholds,
quantized replica). Each tier resolves its own validated params, scorers
and lazily-built jitted closures against the SAME index arrays, result
cache keys carry the serving tier (a degraded answer can never be served
as a full-quality hit), and all tier planners dispatch off ONE shared
plan cache (the routing bound is tier-invariant). The SLO scheduler
(``serve/scheduler.py``) is the component that steps requests down the
ladder under load.

**Observability** (DESIGN.md §3): ``search``, ``search_expr`` and
``flush`` run under a ``khi.search`` profiler span
(``jax.profiler.TraceAnnotation``, args ``seq``, ``lanes``, ``tier``)
that holds ``khi.cache`` (result-cache hashing, lookups and puts), the
Planner's ``khi.plan``, one ``khi.dispatch`` / ``khi.fetch`` pair per
device program run (arg ``program``) and ``khi.merge`` (host merges);
the graph program names its two phases with the ``khi_router`` and
``khi_hop_loop`` scopes. ``stats`` counts the graph program's work,
beside ``scan_lanes``: ``graph_lanes`` (real lanes that ran the graph
program), ``graph_hops`` (their hops), ``graph_lane_steps`` (per graph
program run, the lanes it ran, pads included, times its largest hop
count: the lane-iterations the lockstep loop paid for), ``graph_runs``
(graph program runs), and per run what ``engine.graph_counters``
returns: ``router_chunks`` (the chunk iterations the level router's
lockstep loops ran: per tree level, the largest ceil(live frontier /
chunk) over the run's lanes, summed over levels; ``core/router.py``),
``router_live`` (per level the largest live frontier, summed),
``router_scan_steps`` (per level the most ``first_in_box`` steps a
lane's entry scans took, summed), ``graph_fresh`` (over the hop loop,
neighbours first seen and not yet visited) and ``graph_inbox`` (those
of them in the box). The graph program's router scope holds a
``khi_entry_scan`` scope around its entry scans. Under ``mesh=`` these
never reach the host, so they do not move there. ``last_routes`` /
``last_hops`` hold the last batch's per-lane dispatch record.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import time
from typing import Iterable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core.delta import StreamingState
from ..core.engine import (SCAN_BACKENDS, DeviceIndex, Planner, SearchParams,
                           GRAPH_COUNTERS, _fetch, _merge_dedup,
                           _query_one, device_put_index, graph_counters,
                           resolve_scorer_pair, validate_search_params,
                           with_quant_replica)
from ..core.khi import KHIConfig, KHIIndex
from ..core.predicate import canonical_key, compile_expr, validate_expr
from ..core.sharded import (ShardedKHI, _merge_topk, _shard_search,
                            build_sharded)

__all__ = ["ServeConfig", "Request", "Result", "KHIService"]


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Service-level knobs (index/search knobs live in SearchParams)."""

    buckets: Tuple[int, ...] = (1, 8, 32, 128)  # padded batch shapes
    cache_size: int = 4096                      # LRU entries; 0 disables

    def __post_init__(self):
        if not self.buckets or list(self.buckets) != sorted(set(self.buckets)) \
                or self.buckets[0] <= 0:
            raise ValueError("buckets must be a sorted tuple of distinct "
                             f"positive sizes, got {self.buckets!r}")
        if self.cache_size < 0:
            raise ValueError(f"cache_size must be >= 0 (0 disables), got "
                             f"{self.cache_size}")

    @property
    def max_batch(self) -> int:
        return self.buckets[-1]


@dataclasses.dataclass
class Request:
    """One RFANNS query: vector + exactly ONE filter form — a
    per-attribute [lo, hi] box (``lo``/``hi``), or a boolean predicate
    expression (``expr=``, DESIGN.md §15) that the compiler lowers to a
    disjoint box cover / bitmask program at serve time."""

    query: np.ndarray                 # (d,) float32
    lo: Optional[np.ndarray] = None   # (m,) float32, -inf = unconstrained
    hi: Optional[np.ndarray] = None   # (m,) float32, +inf = unconstrained
    expr: Optional[object] = None     # core.predicate.Expr

    def __post_init__(self):
        if self.expr is None:
            if self.lo is None or self.hi is None:
                raise ValueError(
                    "Request needs a filter: pass both lo= and hi= (range "
                    "box) or expr= (predicate expression, DESIGN.md §15)")
        elif self.lo is not None or self.hi is not None:
            raise ValueError(
                "Request mixes expr= with lo/hi — a compiled predicate "
                "already encodes its boxes; pass exactly one filter form")


@dataclasses.dataclass
class Result:
    ids: np.ndarray    # (k,) int32 global object ids, -1 padded
    dists: np.ndarray  # (k,) float32 squared L2, inf padded
    cached: bool = False
    # with streaming enabled, ids are (k,) int64 stable EXTERNAL ids
    # (DESIGN.md §11) — they survive compaction epochs


class KHIService:
    """Micro-batching, caching front-end over a (sharded) KHI index.

    Accepts a host ``KHIIndex``, a flattened ``DeviceIndex`` (single shard),
    or a ``ShardedKHI`` (leading-axis shard stack). Three entry points:

      * ``search(queries, lo, hi)``  — batch-in, batch-out;
      * ``submit(req)`` + ``flush()`` — explicit queueing;
      * ``serve_stream(reqs)``       — iterator in, results out, batches of
                                       up to ``config.max_batch``.
    """

    def __init__(self, index, params: Optional[SearchParams] = None, *,
                 config: Optional[ServeConfig] = None, mesh=None,
                 dist_fn=None, on_undersized: str = "adjust",
                 tiers: Sequence[SearchParams] = (),
                 interpret: Optional[bool] = None):
        if on_undersized not in ("raise", "adjust", "ignore"):
            # fail at construction, not on the first undersized search
            raise ValueError(f"on_undersized must be raise|adjust|ignore, "
                             f"got {on_undersized!r}")
        self._tier_user: Tuple[SearchParams, ...] = (
            params or SearchParams(),) + tuple(tiers)
        self._check_tiers(self._tier_user)
        self._on_undersized = on_undersized
        self.config = config or ServeConfig()
        self._legacy_dist_fn = dist_fn
        # Pallas interpret mode: None = interpreter off the TPU only
        self._interpret = interpret
        self._mesh = mesh
        self.epoch = 0
        self._cache: "collections.OrderedDict[bytes, Tuple[np.ndarray, np.ndarray]]" = (
            collections.OrderedDict())
        self._pending: List[Tuple[int, Request]] = []
        self._next_ticket = 0
        self.stats = {
            "requests": 0, "cache_hits": 0, "batches": 0, "pad_lanes": 0,
            "traced_buckets": set(), "epoch_swaps": 0, "scan_lanes": 0,
            "graph_lanes": 0, "graph_hops": 0, "graph_lane_steps": 0,
            "graph_runs": 0, **dict.fromkeys(GRAPH_COUNTERS, 0),
            "inserts": 0, "deletes": 0, "compactions": 0,
            "ingest_seconds": 0.0, "compact_seconds": 0.0,
            "tier_lanes": collections.Counter(),
            "predicate_lanes": collections.Counter(),
        }
        # per-lane dispatch record of the last batch ``_answer`` served
        # (``search``, each box of ``search_expr``, ``flush``): each lane's
        # route ("cache" for a hit, else ``_routes``) and its hops (-1
        # where the host never sees them: under mesh=)
        self.last_routes = np.zeros((0,), "U6")
        self.last_hops = np.zeros((0,), np.int32)
        self._seq = 0               # khi.search span sequence number
        # set to stats["predicate_lanes"] for the duration of a compiled-
        # predicate run so the dispatch chokepoints attribute their device
        # lanes to it (DESIGN.md §15); None outside search_expr
        self._pred_lanes: Optional[collections.Counter] = None
        self._stream: Optional[StreamingState] = None
        self._mutation_seq = 0      # cache-key component (DESIGN.md §11)
        self._compacting = False
        self._install_index(index)

    @staticmethod
    def _check_tiers(tier_user: Tuple[SearchParams, ...]) -> None:
        """Ladder-coherence rules (DESIGN.md §13): a degraded tier may
        trade recall for speed but must keep the result CONTRACT of tier
        0 — same k (Result shapes, cache entries and the streaming merge
        are all k-shaped) and one replica dtype across quantized tiers
        (the index carries a single compressed replica)."""
        base = tier_user[0]
        for t, p in enumerate(tier_user[1:], start=1):
            if p.k != base.k:
                raise ValueError(
                    f"degradation tier {t} changes k ({p.k} != {base.k}): "
                    f"tiers degrade recall, never the result shape")
        quants = {p.quant for p in tier_user if p.quant != "none"}
        if len(quants) > 1:
            raise ValueError(
                f"degradation tiers mix quantized replicas {sorted(quants)}; "
                f"the index carries one compressed replica — use a single "
                f"quant across the ladder")

    def set_tiers(self, tiers: Sequence[SearchParams]) -> None:
        """(Re)install the degradation ladder (DESIGN.md §13): tier 0
        stays the construction-time params, ``tiers[i]`` becomes ladder
        step ``i+1``. Rebuilds the per-tier closures against the live
        index; the result cache stays valid (keys carry the serving
        tier's params)."""
        new = (self._tier_user[0],) + tuple(tiers)
        self._check_tiers(new)
        self._tier_user = new
        self._install_index(self.index)

    @property
    def n_tiers(self) -> int:
        return len(self._tier_user)

    def _install_index(self, index) -> None:
        """Bind an index: resolve every tier's params against it and reset
        the per-tier closure/planner caches (closures JIT lazily per tier
        — an unused ladder step costs nothing). Shared by __init__,
        set_tiers and swap_index."""
        if isinstance(index, KHIIndex):
            index = device_put_index(index)
        self._sharded = isinstance(index, ShardedKHI)
        di = index.di if self._sharded else index
        if self._mesh is not None and not self._sharded:
            raise ValueError(
                "mesh= serving needs a ShardedKHI (the collective shard_map "
                "program shards the stacked index over the model axis — "
                "DESIGN.md §14)")
        tier_params = []
        for t, up in enumerate(self._tier_user):
            tier_params.append(validate_search_params(
                up, di, on_undersized=self._on_undersized))
        # quantized score path (DESIGN.md §12): attach the compressed
        # replica the scorers stream (any tier that wants it — ladder
        # coherence pins a single quant); swap_index/compact re-derive it
        # for every new epoch through this same path
        quants = {p.quant for p in tier_params if p.quant != "none"}
        if quants and di.qvecs is None:
            di = with_quant_replica(di, next(iter(quants)))
            index = (dataclasses.replace(index, di=di) if self._sharded
                     else di)
        self._tier_params: Tuple[SearchParams, ...] = tuple(tier_params)
        self.params = tier_params[0]
        self.index = index
        # one plan cache across every tier's planner (DESIGN.md §13): the
        # cached routing bound is tier-invariant, so a box estimated at
        # full quality re-dispatches for free at every degraded tier
        self._plan_cache: "collections.OrderedDict[bytes, int]" = (
            collections.OrderedDict())
        self._planners: dict = {}
        self._pred_planners: dict = {}   # bitmask-fallback tiers (§15)
        self._search_fns: dict = {}
        self._hlo_fns: dict = {}         # tier -> batch -> {program: HLO}
        self._search = self._get_search_fn(0)   # prebuild the hot tier

    def swap_index(self, index, *, params: Optional[SearchParams] = None,
                   drain: bool = True) -> dict:
        """Epoch hot-swap: atomically replace the live index with a freshly
        (re)built one (KHIIndex / DeviceIndex / ShardedKHI — shardedness may
        change across epochs).

        By default any queued requests are flushed against the *old* index
        first (they targeted it) and their results returned, so nothing is
        dropped; pass ``drain=False`` to let them run on the new epoch at
        the next flush instead. The result cache is invalidated per epoch:
        the epoch is part of every cache key (stale entries are
        unreachable) and the store is cleared eagerly. Returns the drained
        ``{ticket: Result}`` dict (empty when nothing was pending).

        With streaming enabled a bare swap would orphan the delta rows and
        the ext-id mapping — ``compact()`` is the only sanctioned publisher
        of new epochs then (DESIGN.md §11).
        """
        if self._stream is not None and not self._compacting:
            raise RuntimeError(
                "swap_index while streaming is enabled would drop the delta "
                "segment and the ext-id mapping; publish new epochs through "
                "compact() (DESIGN.md §11)")
        drained = self.flush() if drain else {}
        if params is not None:
            new = (params,) + self._tier_user[1:]
            self._check_tiers(new)
            self._tier_user = new
        self._install_index(index)
        self.epoch += 1
        self._cache.clear()
        self.stats["epoch_swaps"] += 1
        return drained

    # ------------------------------------------------------------- plumbing
    @property
    def _planner(self) -> Optional[Planner]:
        """Tier-0 planner (None on strategy='graph' or before first use)."""
        return self._planners.get(0)

    @property
    def d(self) -> int:
        return self.index.di.vecs.shape[-1] if self._sharded \
            else self.index.vecs.shape[-1]

    @property
    def m(self) -> int:
        return self.index.di.attrs.shape[-1] if self._sharded \
            else self.index.attrs.shape[-1]

    def _get_search_fn(self, tier: int):
        """Per-tier search closure, built lazily (DESIGN.md §13): an
        unused ladder step never traces."""
        fn = self._search_fns.get(tier)
        if fn is None:
            fn = self._search_fns[tier] = self._build_search_fn(tier)
        return fn

    def _build_search_fn(self, tier: int = 0):
        # Each closure takes one bucket-padded host batch and returns host
        # (ids, dists, hops, plan, counters): hops (bucket,) is None
        # where the host never sees it (mesh=, where counters is empty),
        # plan the Planner's Plan or None, counters the graph program's
        # GRAPH_COUNTERS by name.
        # Every branch reads ``self.index`` at CALL time (not build time):
        # a streaming delete installs a functionally-updated pytree of
        # identical shapes, which the jitted programs must pick up without
        # a rebuild. The old-epoch drain in swap_index still runs against
        # the old index — the flush happens before _install_index rebinds.
        p = self._tier_params[tier]
        scorer, exact = resolve_scorer_pair(p, dist_fn=self._legacy_dist_fn,
                                            interpret=self._interpret)
        if self._mesh is not None:
            # collective pipeline (DESIGN.md §14): every strategy and
            # quant tier lowers through one shard_map program — planner
            # dispatch runs in-collective (psum'ed routing bounds), so
            # there is no host Plan and no per-lane scan_lanes stat here
            from ..core.sharded import make_sharded_search_fn
            fn = make_sharded_search_fn(p, self._mesh,
                                        dist_fn=self._legacy_dist_fn,
                                        skhi=self.index,
                                        on_undersized=self._on_undersized,
                                        interpret=self._interpret)
            self._hlo_fns[tier] = self._jit_hlo("collective", fn)
            return self._device_run("collective", fn)
        if p.strategy != "graph":
            # planner-backed path (DESIGN.md §10): per-lane dispatch to the
            # graph engine or the exact brute scan, single or sharded —
            # params are already validated, the planner re-checks cheaply.
            # Every tier's planner shares ONE plan cache (§13): the cached
            # routing bound is box-keyed and tier-invariant.
            planner = Planner(self.index, p, dist_fn=self._legacy_dist_fn,
                              interpret=self._interpret,
                              on_undersized=self._on_undersized,
                              plan_cache=self._plan_cache,
                              plan_salt=self.epoch.to_bytes(8, "little"))
            self._hlo_fns[tier] = planner.compiled_text
            if self._stream is not None:
                # a tier first used after streaming deletes must see the
                # tombstone-adjusted cardinalities (DESIGN.md §11)
                planner.refresh_index(
                    self.index, deleted_rows=self._stream.deleted_locals())
            self._planners[tier] = planner

            def run(q, lo, hi):
                ids, dists, hops, plan = planner.search(q, lo, hi)
                self.stats["scan_lanes"] += int(plan.use_scan.sum())
                if self._pred_lanes is not None:
                    # compiled-predicate observability (§15): fold this
                    # box's per-lane dispatch into predicate_lanes
                    Planner._count_lanes(plan, self._pred_lanes, q.shape[0])
                return ids, dists, hops, plan, plan.counters

            return run
        # strategy="graph": every lane runs the graph program, which
        # returns per-lane hops (a lane's max over shards, as the
        # Planner's sharded program does)
        if not self._sharded:
            @jax.jit
            def single(di: DeviceIndex, q, qlo, qhi):
                fn = functools.partial(_query_one, p=p, scorer=scorer,
                                       exact_scorer=exact)
                ids, dists, hops, counts = jax.vmap(
                    lambda qq, lo, hi: fn(di, qq, lo, hi))(q, qlo, qhi)
                return ids, dists, hops, graph_counters(counts)

            self._hlo_fns[tier] = self._jit_hlo("graph", single)
            return self._device_run("graph", single)

        n_shards = self.index.num_shards

        @jax.jit
        def fanout(skhi: ShardedKHI, q, qlo, qhi):
            def per_shard(di, off):
                return _shard_search(di, off, n_shards, q, qlo, qhi,
                                     p, scorer, exact_scorer=exact)
            gids, dists, hops, counts = jax.vmap(per_shard)(skhi.di,
                                                            skhi.offsets)
            return _merge_topk(gids, dists, p.k) + (
                jnp.max(hops, axis=0), graph_counters(counts))

        self._hlo_fns[tier] = self._jit_hlo("graph", fanout)
        return self._device_run("graph", fanout)

    def _device_run(self, program: str, fn):
        """Host closure over a whole-batch program ``fn(index, q, lo, hi)``
        that returns (ids, dists) or (ids, dists, hops, graph_counters),
        under the ``khi.dispatch`` / ``khi.fetch`` profiler spans."""
        def run(q, lo, hi):
            with TraceAnnotation("khi.dispatch", program=program):
                out = fn(self.index, jnp.asarray(q), jnp.asarray(lo),
                         jnp.asarray(hi))
            ids, dists, *graph = _fetch(program, out)
            if not graph:
                return ids, dists, None, None, {}
            return ids, dists, graph[0], None, dict(
                zip(GRAPH_COUNTERS, graph[1].tolist()))
        return run

    def _jit_hlo(self, name: str, fn):
        """batch -> {name: compiled HLO text} for a program called as
        ``fn(self.index, q, lo, hi)``."""
        def hlo(batch: int) -> dict:
            q = jax.ShapeDtypeStruct((batch, self.d), jnp.float32)
            box = jax.ShapeDtypeStruct((batch, self.m), jnp.float32)
            return {name: fn.lower(self.index, q, box, box).compile()
                    .as_text()}
        return hlo

    def compiled_hlo(self, batch: int, tier: int = 0) -> dict:
        """Compiled HLO text of every whole-batch device program serving
        ``tier`` at a ``batch``-lane bucket, by program name — what a
        caller checks to see that the Pallas kernels lowered to Mosaic
        (``tpu_custom_call``) rather than running interpreted."""
        self._get_search_fn(tier)
        return self._hlo_fns[tier](batch)

    def _bucket(self, b: int) -> int:
        for size in self.config.buckets:
            if b <= size:
                return size
        return self.config.max_batch

    def _key(self, q: np.ndarray, lo: np.ndarray, hi: np.ndarray,
             tier: int = 0) -> bytes:
        h = hashlib.blake2b(digest_size=16)
        h.update(q.tobytes())
        h.update(lo.tobytes())
        h.update(hi.tobytes())
        # the serving TIER is part of the key (index + params — two tiers
        # with identical params still key apart): an answer degraded under
        # load must never be served later as a full-quality hit, and vice
        # versa (DESIGN.md §13)
        h.update(tier.to_bytes(2, "little"))
        h.update(repr(self._tier_params[tier]).encode())
        h.update(self.epoch.to_bytes(8, "little"))  # per-epoch invalidation
        # per-mutation invalidation: every insert/delete/compact bumps the
        # sequence, so stale pre-mutation results are unreachable even
        # within one epoch (DESIGN.md §11)
        h.update(self._mutation_seq.to_bytes(8, "little"))
        return h.digest()

    def _cache_get(self, key: bytes):
        if not self.config.cache_size:
            return None
        hit = self._cache.get(key)
        if hit is not None:
            self._cache.move_to_end(key)
        return hit

    def _cache_put(self, key: bytes, ids: np.ndarray, dists: np.ndarray):
        if not self.config.cache_size:
            return
        self._cache[key] = (ids, dists)
        self._cache.move_to_end(key)
        while len(self._cache) > self.config.cache_size:
            self._cache.popitem(last=False)

    # ----------------------------------------------------------- device run
    def _run_device(self, qs: np.ndarray, los: np.ndarray,
                    his: np.ndarray, tier: int = 0):
        """Pad one micro-batch to its bucket, search at ``tier``, unpad:
        -> (ids, dists, hops, routes), each (b,) per lane; hops is None
        under mesh=."""
        b = qs.shape[0]
        bucket = self._bucket(b)
        pad = bucket - b
        if pad:
            qs = np.concatenate([qs, np.zeros((pad, self.d), np.float32)])
            # empty range: RangeFilter yields no entries, loop exits at once
            los = np.concatenate(
                [los, np.full((pad, self.m), np.inf, np.float32)])
            his = np.concatenate(
                [his, np.full((pad, self.m), -np.inf, np.float32)])
        search = self._search if tier == 0 else self._get_search_fn(tier)
        ids, dists, hops, plan, counters = search(qs, los, his)
        if self._stream is not None:
            # windowed merge (DESIGN.md §11): fold the per-shard delta
            # scans into the epoch results on the bucket-padded batch (the
            # delta scan traces per bucket shape too; pad lanes carry the
            # empty box and contribute nothing), then unpad. Ids become
            # stable int64 ext ids here.
            with TraceAnnotation("khi.merge"):
                ids, dists = self._stream.merge(ids, dists, qs, los, his,
                                                self.params.k)
        self.stats["batches"] += 1
        self.stats["pad_lanes"] += pad
        self.stats["traced_buckets"].add(bucket)
        self.stats["tier_lanes"][tier] += b
        if self._pred_lanes is not None \
                and self._tier_params[tier].strategy == "graph":
            # strategy="graph" has no per-lane Plan — every device lane of
            # a predicate box (pads included) is a graph lane (§15)
            self._pred_lanes["graph"] += bucket
        routes = self._routes(plan, bucket, hops is None)[:b]
        if hops is not None:
            hops = hops[:b]
            self._count_graph(plan, hops, routes, bucket, counters)
        return ids[:b], dists[:b], hops, routes

    @staticmethod
    def _routes(plan, bucket: int, mesh: bool) -> np.ndarray:
        """Each device lane's route: "mesh" where the dispatch ran inside
        the collective program, else "graph", "scan", or under
        strategy="hybrid" "window" (windows only) or "mixed" (graph walk
        plus windows)."""
        if mesh:
            return np.full(bucket, "mesh")
        if plan is None:
            return np.full(bucket, "graph")
        if plan.mode is not None:
            return np.array(["graph", "window", "mixed"])[plan.mode]
        return np.where(plan.use_scan, "scan", "graph")

    def _count_graph(self, plan, hops: np.ndarray, routes: np.ndarray,
                     bucket: int, counters: dict) -> None:
        """Graph-program counters of one device batch, ``hops`` and
        ``routes`` of its real lanes: graph_lanes, graph_hops (scan and
        window lanes carry 0), graph_lane_steps — for each graph program
        run, the lanes it ran (pads included) times its largest hop
        count — graph_runs, and its runs' ``GRAPH_COUNTERS``."""
        self.stats["graph_lanes"] += int(
            np.isin(routes, ("graph", "mixed")).sum())
        self.stats["graph_hops"] += int(hops.sum())
        runs = plan.graph_runs if plan is not None else [
            (bucket, int(hops.max(initial=0)))]
        self.stats["graph_lane_steps"] += sum(n * h for n, h in runs)
        self.stats["graph_runs"] += len(runs)
        for name, v in counters.items():
            self.stats[name] += v

    # -------------------------------------------------------------- serving
    def _answer(self, queries: np.ndarray, lo: np.ndarray,
                hi: np.ndarray, tier: int = 0
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Cache-aware core: -> (ids (B, k), dists (B, k), hit (B,) bool).
        Batches larger than the top bucket are chunked. ``tier`` selects
        the degradation-ladder params (DESIGN.md §13; 0 = full quality).
        Leaves the batch's per-lane dispatch record in ``last_routes`` /
        ``last_hops``."""
        queries = np.ascontiguousarray(queries, np.float32)
        lo = np.ascontiguousarray(lo, np.float32)
        hi = np.ascontiguousarray(hi, np.float32)
        B = queries.shape[0]
        self.stats["requests"] += B
        k = self.params.k
        id_dtype = np.int64 if self._stream is not None else np.int32
        out_ids = np.full((B, k), -1, id_dtype)
        out_d = np.full((B, k), np.inf, np.float32)
        hit_mask = np.zeros((B,), bool)
        routes = np.full(B, "cache", "U6")
        lane_hops = np.zeros(B, np.int32)

        # skip per-request hashing entirely when the cache is disabled —
        # blake2b over d=768 query bytes is measurable on the hot path
        caching = self.config.cache_size > 0
        miss: List[int] = list(range(B))
        if caching:
            with TraceAnnotation("khi.cache"):
                keys = [self._key(queries[i], lo[i], hi[i], tier)
                        for i in range(B)]
                miss = []
                for i, key in enumerate(keys):
                    hit = self._cache_get(key)
                    if hit is not None:
                        out_ids[i], out_d[i] = hit
                        hit_mask[i] = True
                        self.stats["cache_hits"] += 1
                    else:
                        miss.append(i)

        for c0 in range(0, len(miss), self.config.max_batch):
            chunk = miss[c0:c0 + self.config.max_batch]
            ids, dists, hops, chunk_routes = self._run_device(
                queries[chunk], lo[chunk], hi[chunk], tier)
            routes[chunk] = chunk_routes
            lane_hops[chunk] = -1 if hops is None else hops
            out_ids[chunk], out_d[chunk] = ids, dists
            if caching:
                with TraceAnnotation("khi.cache"):
                    for j, i in enumerate(chunk):
                        self._cache_put(keys[i], ids[j], dists[j])
        self.last_routes, self.last_hops = routes, lane_hops
        return out_ids, out_d, hit_mask

    def search(self, queries: np.ndarray, lo: np.ndarray,
               hi: np.ndarray, *, tier: int = 0
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Batch front door: (B, d) x (B, m) x (B, m) -> ids/dists (B, k).
        ``tier`` serves the batch at that degradation-ladder step
        (DESIGN.md §13) — the SLO scheduler's knob; direct callers keep
        the default full-quality tier 0."""
        if not 0 <= tier < len(self._tier_params):
            raise ValueError(f"tier must be in [0, {len(self._tier_params)})"
                             f", got {tier} (install ladders via tiers= / "
                             f"set_tiers)")
        with self._search_span(len(queries), tier):
            ids, dists, _ = self._answer(queries, lo, hi, tier)
        return ids, dists

    def _search_span(self, lanes: int, tier: int) -> TraceAnnotation:
        """The ``khi.search`` profiler span around one front-door call;
        its ``seq`` names the batch every inner ``khi.*`` span belongs
        to."""
        self._seq += 1
        return TraceAnnotation("khi.search", seq=self._seq, lanes=lanes,
                               tier=tier)

    # ------------------------------------------- compiled predicates (§15)
    def _pred_planner(self, tier: int) -> Planner:
        """Planner executing the bitmask-fallback program at ``tier``.
        Reuses the dispatch planner when the tier already built one
        (strategy != "graph"); otherwise builds a dedicated instance
        lazily — reset on every epoch swap by ``_install_index``."""
        planner = self._planners.get(tier) or self._pred_planners.get(tier)
        if planner is None:
            planner = Planner(
                self.index, self._tier_params[tier],
                dist_fn=self._legacy_dist_fn,
                on_undersized=self._on_undersized,
                plan_cache=self._plan_cache,
                plan_salt=self.epoch.to_bytes(8, "little"))
            self._pred_planners[tier] = planner
        return planner

    def search_expr(self, queries: np.ndarray, expr, *, tier: int = 0
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Predicate front door (DESIGN.md §15): (B, d) queries × one
        boolean filter expression -> ids/dists (B, k).

        Box-mode programs serve each disjoint disjunct through the normal
        cached/bucketed/stream-merged ``_answer`` path and merge the
        per-box streams with ``_merge_dedup`` (int64 ext ids under
        streaming); bitmask fallbacks run one exact f32 scan through the
        tier's Planner. ``stats["predicate_lanes"]`` picks up the per-
        strategy device-lane counts either way."""
        if not 0 <= tier < len(self._tier_params):
            raise ValueError(f"tier must be in [0, {len(self._tier_params)})"
                             f", got {tier} (install ladders via tiers= / "
                             f"set_tiers)")
        if self._mesh is not None:
            raise ValueError(
                "search_expr with mesh=: compiled predicates do not lower "
                "through the collective shard_map program yet — the per-"
                "disjunct dispatch and the dedup merge run host-side. "
                "Serve predicates without a mesh (vmap fan-out answers a "
                "ShardedKHI with identical semantics), or pre-lower the "
                "expression with core.predicate.compile_expr and issue its "
                "boxes as plain search() calls (DESIGN.md §15)")
        validate_expr(expr, self.m)
        queries = np.ascontiguousarray(queries, np.float32)
        with self._search_span(queries.shape[0], tier):
            return self._search_expr(queries, expr, tier)

    def _search_expr(self, queries: np.ndarray, expr, tier: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
        B, k = queries.shape[0], self.params.k
        p = self._tier_params[tier]
        prog = compile_expr(expr, self.m, box_budget=p.box_budget)
        if prog.mode == "bitmask":
            if self._stream is not None:
                raise ValueError(
                    f"predicate compiled to the bitmask fallback (cover "
                    f"exceeds box_budget={p.box_budget}) while streaming "
                    f"is enabled: the host mask plane cannot see delta "
                    f"rows (DESIGN.md §11/§15). Raise "
                    f"SearchParams.box_budget so the cover fits, simplify "
                    f"the expression, or compact() first")
            self.stats["requests"] += B
            self.stats["predicate_lanes"]["bitmask"] += B
            ids, dists, _hops = self._pred_planner(tier)._run_mask(
                queries, prog)
            return ids, dists
        id_dtype = np.int64 if self._stream is not None else np.int32
        out_ids = np.full((B, k), -1, id_dtype)
        out_d = np.full((B, k), np.inf, np.float32)
        m = self.m
        self._pred_lanes = self.stats["predicate_lanes"]
        try:
            for b in range(prog.n_boxes):
                lo = np.ascontiguousarray(
                    np.broadcast_to(prog.lo[b], (B, m)), np.float32)
                hi = np.ascontiguousarray(
                    np.broadcast_to(prog.hi[b], (B, m)), np.float32)
                ids, dists, _hit = self._answer(queries, lo, hi, tier)
                if b == 0:
                    out_ids, out_d = ids.astype(id_dtype), dists
                else:
                    # disjoint cover: no row appears under two boxes, so
                    # best-dist-per-id dedup only collapses (-1, inf) pads
                    with TraceAnnotation("khi.merge"):
                        out_ids, out_d = _merge_dedup(out_ids, out_d, ids,
                                                      dists, k,
                                                      out_dtype=id_dtype)
        finally:
            self._pred_lanes = None
        return out_ids, out_d

    def submit(self, req: Request) -> int:
        """Enqueue one request; returns a ticket for flush()'s result list."""
        ticket = self._next_ticket
        self._next_ticket += 1
        self._pending.append((ticket, req))
        return ticket

    def _run_batch(self, batch: Sequence[Request]) -> List[Result]:
        """Answer one mixed batch of box and predicate requests (§15).

        Box requests run as ONE micro-batch through ``_answer``;
        predicate requests are grouped by the expression's canonical key
        (``parse_expr("a0>=1 and a0<=2")`` and ``Range(0, 1, 2)`` share a
        compiled program and a group) and each group serves as its own
        ``search_expr`` batch. Predicate Results report ``cached=False``
        — the per-box answers still hit the LRU underneath, but a merged
        multi-box result is not itself a single cache entry."""
        results: List[Optional[Result]] = [None] * len(batch)
        box_idx = [j for j, r in enumerate(batch) if r.expr is None]
        if box_idx:
            qs = np.stack([batch[j].query for j in box_idx]).astype(np.float32)
            los = np.stack([batch[j].lo for j in box_idx]).astype(np.float32)
            his = np.stack([batch[j].hi for j in box_idx]).astype(np.float32)
            with self._search_span(len(box_idx), 0):
                ids, dists, hit = self._answer(qs, los, his)
            for i, j in enumerate(box_idx):
                results[j] = Result(ids=ids[i], dists=dists[i],
                                    cached=bool(hit[i]))
        groups: "collections.OrderedDict[bytes, List[int]]" = (
            collections.OrderedDict())
        for j, r in enumerate(batch):
            if r.expr is not None:
                groups.setdefault(canonical_key(r.expr), []).append(j)
        for idx in groups.values():
            qs = np.stack([batch[j].query for j in idx]).astype(np.float32)
            ids, dists = self.search_expr(qs, batch[idx[0]].expr)
            for i, j in enumerate(idx):
                results[j] = Result(ids=ids[i], dists=dists[i])
        return results

    def flush(self) -> dict:
        """Run all pending requests (micro-batched); {ticket: Result}."""
        if not self._pending:
            return {}
        pending, self._pending = self._pending, []
        results = self._run_batch([r for _, r in pending])
        return {ticket: results[j]
                for j, (ticket, _) in enumerate(pending)}

    def serve_stream(self, requests: Iterable[Request]) -> Iterator[Result]:
        """Consume an iterator of requests, yield Results in order,
        micro-batching up to ``config.max_batch`` at a time."""
        batch: List[Request] = []
        for req in requests:
            batch.append(req)
            if len(batch) >= self.config.max_batch:
                yield from self._run_batch(batch)
                batch = []
        if batch:
            yield from self._run_batch(batch)

    # ---------------------------------------------------------- streaming
    def enable_streaming(self, *, capacity: int = 4096,
                         build_config: Optional[KHIConfig] = None
                         ) -> StreamingState:
        """Turn on the streaming write path (DESIGN.md §11): per-shard
        device delta segments of ``capacity`` rows each, tombstoned
        deletes, and ``compact()`` epoch publishing. Query results switch
        to stable int64 EXTERNAL ids (the seed corpus keeps ``0..n-1``).
        ``build_config`` is what compaction rebuilds with — default the
        PR-2 device bulk builder; pass the original build config when
        bit-identical no-op compaction matters (tests/test_streaming.py).
        """
        if self._stream is not None:
            raise RuntimeError("streaming is already enabled")
        if self._mesh is not None:
            raise ValueError(
                "streaming with mesh=: the delta merge runs on the host "
                "after the collective fan-out returns — serve without a "
                "mesh (vmap fan-out) to stream (DESIGN.md §11)")
        backend = (self.params.backend
                   if self.params.backend in SCAN_BACKENDS else "jnp")
        self._stream = StreamingState(
            self.index, capacity=capacity,
            build_config=build_config or KHIConfig(builder="device"),
            backend=backend, quant=self.params.quant,
            rerank_mult=self.params.rerank_mult)
        self._note_mutation()
        return self._stream

    def _require_stream(self) -> StreamingState:
        if self._stream is None:
            raise RuntimeError("call enable_streaming() first")
        return self._stream

    def _note_mutation(self) -> None:
        """Every mutation bumps the cache-key sequence; eager clear keeps
        the store from holding unreachable entries."""
        self._mutation_seq += 1
        self._cache.clear()

    def insert(self, vecs: np.ndarray, attrs: np.ndarray) -> np.ndarray:
        """Append rows to the delta; returns their stable int64 ext ids.
        Auto-compacts first when the batch would not fit the per-shard
        deltas (the windowed-merge cadence, DESIGN.md §11)."""
        st = self._require_stream()
        vecs = np.ascontiguousarray(np.atleast_2d(vecs), np.float32)
        attrs = np.ascontiguousarray(np.atleast_2d(attrs), np.float32)
        b = vecs.shape[0]
        t0 = time.perf_counter()
        if not st.fits(b):
            self.compact()
            if not st.fits(b):
                raise ValueError(
                    f"insert batch of {b} rows cannot fit the per-shard "
                    f"delta capacity {st.deltas[0].capacity} even after "
                    f"compaction")
        exts = st.insert(vecs, attrs)
        self.stats["inserts"] += b
        self.stats["ingest_seconds"] += time.perf_counter() - t0
        self._note_mutation()
        return exts

    def delete(self, ext_ids) -> int:
        """Tombstone rows by ext id (unknown / already-dead ids are
        skipped). Delta rows NaN their buffer slots; base rows NaN their
        attr row in a functionally-updated index pytree that every search
        path — both fused kernels included — masks out via the NaN lane
        convention, and the planner's cardinality estimators are refreshed
        so dead rows never inflate dispatch (DESIGN.md §11). Returns the
        number of rows actually deleted."""
        st = self._require_stream()
        t0 = time.perf_counter()
        new_index, n_del = st.delete(np.asarray(ext_ids), self.index)
        if new_index is not None:
            self.index = new_index
            for planner in self._planners.values():
                planner.refresh_index(
                    new_index, deleted_rows=st.deleted_locals())
        self.stats["deletes"] += n_del
        self.stats["ingest_seconds"] += time.perf_counter() - t0
        if n_del:
            self._note_mutation()
        return n_del

    def compact(self) -> dict:
        """Fold delta + tombstones into a fresh epoch: gather the live
        corpus, rebuild with the stored build config (device bulk builder
        by default), publish through the ``swap_index`` drain protocol —
        queued requests flush against the OLD delta-merged view first, so
        compaction never changes an already-submitted request's answer —
        then rebind the ext mapping. Returns the drained {ticket: Result}
        dict, like swap_index."""
        st = self._require_stream()
        t0 = time.perf_counter()
        vecs, attrs, exts = st.live_corpus(self.index)
        if not vecs.shape[0]:
            raise ValueError("cannot compact an index down to zero live "
                             "rows (delete less or rebuild explicitly)")
        if st.S > 1:
            new_index = build_sharded(vecs, attrs, st.S, st.build_config)
        else:
            new_index = KHIIndex.build(vecs, attrs, st.build_config)
        self._compacting = True
        try:
            drained = self.swap_index(new_index)
        finally:
            self._compacting = False
        st.reset(self.index, exts)
        self.stats["compactions"] += 1
        self.stats["compact_seconds"] += time.perf_counter() - t0
        self._note_mutation()
        return drained

    # ------------------------------------------------------------- metrics
    def snapshot(self) -> dict:
        """JSON-able stats snapshot (traced_buckets -> sorted list)."""
        s = dict(self.stats)
        s["traced_buckets"] = sorted(s["traced_buckets"])
        s["tier_lanes"] = {str(t): int(n)
                           for t, n in sorted(s["tier_lanes"].items())}
        s["predicate_lanes"] = {str(strat): int(n) for strat, n
                                in sorted(s["predicate_lanes"].items())}
        s["cache_entries"] = len(self._cache)
        s["epoch"] = self.epoch
        if self._stream is not None:
            s["streaming"] = True
            s["n_live"] = self._stream.n_live
            s["delta_fill"] = [seg.size for seg in self._stream.deltas]
            s["tombstones"] = int(self._stream.base_deleted.sum())
        return s
