"""Jitted, batched KHI query engine — the TPU-native form of Algorithms 1-3,
structured as an explicit **two-phase pipeline** (DESIGN.md §9) behind a
**selectivity-adaptive planner** (DESIGN.md §10; ``Planner`` at the end
of this module): ``SearchParams.strategy`` dispatches each query to this
graph program, to the exact predicate-fused brute scan
(``kernels/scan_topk.py``), or — ``"auto"`` — per query on the routing
sweep's in-range cardinality bound. The graph program:

  * **Phase A — routing** (``core.router``): Algorithm 1 as a
    level-synchronous batched frontier sweep over the flattened tree
    (``SearchParams.router="level"``, the production default: a fixed
    ``fori_loop`` over the O(log n) tree levels with per-level batched
    entry scans), or the legacy per-query stack-DFS ``while_loop``
    (``router="dfs"``). Both return identical entry vectors.
  * **Phase B — filtered greedy search** on a pluggable ``Scorer``: the
    wide-frontier hop loop (DESIGN.md §8) with candidate scoring behind
    one registry contract (below).

Everything is a fixed-shape array program (see DESIGN.md §2):

  * ReconsNbr's early-exit   -> gather all H*M neighbor ids at once, then an
                                exclusive-cumsum prefix cap reproduces the
                                sequential c_n budget *and* its partial
                                visited-marking semantics exactly;
  * the two priority queues  -> one distance-sorted pool of size ef with
                                expanded flags (beam form; equivalent to
                                Alg. 3 because R-hat never shrinks, so
                                candidates worse than the ef-th best can
                                never be expanded);
  * visited set              -> dense per-query bool mask (n,).

The inner loop is a **wide frontier** (DESIGN.md §8): every hop expands the
top-``expand_width`` unexpanded pool entries at once, fuses their E*H*M
neighbor rows into one candidate stream (scatter-based first-occurrence
dedup, per-expansion c_n budgets), and evaluates all surviving candidates
in a single scoring call — so a hop is one fat gather + one MXU-shaped
reduction instead of E narrow ones, and the vmapped batch takes ~E-fold
fewer lockstep iterations. ``expand_width=1`` is bit-identical to the
single-expansion engine (pinned against a committed golden snapshot);
``expand_width>1`` changes hop order only — the matching reference
semantics live in ``query_ref.query(expand_width=)``.

``search_batch`` vmaps the per-query program and jits the whole thing;
candidate scoring is pluggable (``SearchParams.backend``), unified behind
the ``Scorer`` registry (DESIGN.md §9) — ``score(di, q, qlo, qhi, ids) ->
(C,) f32`` with +inf for -1 (pad) lanes, plus the stream-side predicate
``in_range``:

  * ``"jnp"``              — XLA gather + elementwise reduce (portable
                             reference path; under vmap the gather
                             materializes a (B, C, d) intermediate in HBM);
  * ``"pallas_l2"``        — same materialized gather, but the reduction
                             runs through the MXU-tiled ``l2dist`` kernel;
  * ``"pallas_gather_l2"`` — the fused scalar-prefetch kernel
                             (``kernels.gather_l2``): the candidate id
                             stream drives the DMA index_map, so each row
                             moves HBM->VMEM exactly once and no (B, C, d)
                             gather is ever materialized;
  * ``"pallas_gather_l2_filter"`` — the predicate-fused production
                             default (``kernels.gather_l2_filter``): each
                             candidate's attribute row is DMA'd alongside
                             its vector row, ``all(qlo <= a <= qhi)`` is
                             evaluated in-kernel and out-of-range or pad
                             lanes emit +inf — no separate attrs gather
                             and no caller-side validity overwrite at the
                             scoring site.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

import collections
import hashlib

from . import beam
from .khi import KHIIndex
from .router import (HostCardEstimator, ROUTERS, required_frontier_cap,
                     resolve_router)
from .util import pow2_at_least

__all__ = ["DeviceIndex", "SearchParams", "BACKENDS", "ROUTERS",
           "STRATEGIES", "SCAN_BACKENDS", "DEFAULT_SCAN_FRAC", "QUANTS",
           "Scorer", "Plan", "PredicatePlan", "Planner", "with_quant_replica",
           "device_put_index", "resolve_dist_ids", "resolve_scorer",
           "search_batch", "make_search_fn", "required_scan_budget",
           "required_stack_cap", "required_frontier_cap",
           "derive_search_params", "validate_search_params"]

BACKENDS = ("jnp", "pallas_l2", "pallas_gather_l2", "pallas_gather_l2_filter")

# Execution strategies (DESIGN.md §10, §12): "graph" is the two-phase
# tree-routed greedy search, "scan" the exact predicate-fused brute scan
# (kernels/scan_topk.py), "auto" the per-query planner dispatch on the
# routing sweep's in-range cardinality bound, "hybrid" the per-NODE
# dispatch — small antichain subtrees brute-scan as contiguous DFS
# windows (kernels/scan_topk.py windowed form) while lanes with large
# nodes graph-walk, the two partial top-k streams merging under the
# (dist, id) lexicographic contract.
STRATEGIES = ("graph", "scan", "auto", "hybrid")

# Quantized score-path modes (DESIGN.md §12): the corpus replica the
# scoring kernels stream ("none" = f32 vecs). Non-"none" modes over-fetch
# top-(k * rerank_mult) on the compressed replica and rerank through the
# exact f32 gather path, so final ids/dists stay f32-exact.
QUANTS = ("none", "bf16", "int8")

# Backends the scan strategy can execute on: the scan is predicate-masked
# inside the pass, so it needs either the fused filter kernel or the jnp
# mask oracle — the unfused pallas backends have no in-pass predicate.
SCAN_BACKENDS = ("jnp", "pallas_gather_l2_filter")

# Default dispatch threshold as a fraction of the (total) corpus when
# SearchParams.scan_threshold is 0: scan when the routing bound says at
# most this fraction of objects is in range. 0.1 is the paper-shaped
# crossover (graph traversal degrades below ~10% selectivity — PAPER.md);
# benchmarks/selectivity_bench.py measures the box-specific crossover and
# records it with the committed experiment, and configs/khi_serve.py pins
# the calibrated absolute value for the production cell.
DEFAULT_SCAN_FRAC = 0.1


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceIndex:
    """KHI flattened onto device arrays. A pytree — shard/replicate freely."""

    vecs: jax.Array    # (n, d) float32
    attrs: jax.Array   # (n, m) float32
    nbrs: jax.Array    # (n, H, M) int32  (object-major for one-gather rows)
    # tree
    left: jax.Array    # (P,) int32
    right: jax.Array   # (P,) int32
    dim: jax.Array     # (P,) int32
    bl: jax.Array      # (P,) int32 bitmask
    lo: jax.Array      # (P, m) float32
    hi: jax.Array      # (P, m) float32
    start: jax.Array   # (P,) int32
    count: jax.Array   # (P,) int32
    order: jax.Array   # (n,) int32
    root: jax.Array    # () int32
    # quantized corpus replica (DESIGN.md §12) — None unless
    # SearchParams.quant != "none". ``qvecs`` is (n, d) bf16 or int8;
    # ``qscale`` the int8 per-row (n, 1) f32 scale plane (None for bf16).
    # Trailing optional pytree children: stacking, dataclasses.replace
    # (the streaming tombstone path) and old construction sites all work
    # unchanged.
    qvecs: Optional[jax.Array] = None
    qscale: Optional[jax.Array] = None

    def tree_flatten(self):
        fields = dataclasses.fields(self)
        return tuple(getattr(self, f.name) for f in fields), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def n(self) -> int:
        return self.vecs.shape[0]

    @property
    def height(self) -> int:
        return self.nbrs.shape[1]


# device rows pad to a multiple of 32, whole (8, 128) 32-bit HBM tiles for
# f32 (8 rows), bf16 (16) and int8 (32) alike: the gather kernels DMA a
# candidate's whole tile (kernels/blocks.py), so an aligned corpus is never
# copied inside the hop loop
_ROW_ALIGN = 32


def device_put_index(index: KHIIndex, *, pad_nodes: Optional[int] = None,
                     pad_n: Optional[int] = None,
                     pad_height: Optional[int] = None,
                     vec_dtype=None, quant: str = "none") -> DeviceIndex:
    """Flatten a host KHIIndex into device arrays (optionally padded so that
    multiple shards can be stacked into one leading-axis array). Rows
    always pad up to a multiple of ``_ROW_ALIGN``; pad rows carry +inf
    attrs and no graph edges, and the Planner NaN-masks them for scans.

    ``vec_dtype=jnp.bfloat16`` stores corpus vectors in bf16 (distances still
    accumulate in f32) — halves the dominant HBM term of the search engine
    (§Perf iteration). ``quant`` ("bf16"/"int8") additionally attaches the
    compressed score replica via ``with_quant_replica`` (DESIGN.md §12)."""
    t = index.tree
    n, H = index.n, index.height
    P = t.num_nodes
    nbrs = np.ascontiguousarray(np.transpose(index.nbrs, (1, 0, 2)))  # (n,H,M)

    pn = -(-(pad_n or n) // _ROW_ALIGN) * _ROW_ALIGN
    pP = pad_nodes or P
    pH = pad_height or H

    def padn(a, fill=0):
        out = np.full((pn,) + a.shape[1:], fill, a.dtype)
        out[:n] = a
        return out

    def padp(a, fill=0):
        out = np.full((pP,) + a.shape[1:], fill, a.dtype)
        out[:P] = a
        return out

    nb = np.full((pn, pH, nbrs.shape[2]), -1, np.int32)
    nb[:n, :H] = nbrs
    root = int(np.nonzero(t.parent < 0)[0][0])
    vd = vec_dtype or jnp.float32
    di = DeviceIndex(
        vecs=jnp.asarray(padn(index.vecs), dtype=vd),
        attrs=jnp.asarray(padn(index.attrs, fill=np.float32(np.inf))),
        nbrs=jnp.asarray(nb),
        left=jnp.asarray(padp(t.left, -1)),
        right=jnp.asarray(padp(t.right, -1)),
        dim=jnp.asarray(padp(t.dim, -1)),
        bl=jnp.asarray(padp(t.bl.astype(np.int32), 0)),
        lo=jnp.asarray(padp(t.lo, np.float32(np.inf))),
        hi=jnp.asarray(padp(t.hi, np.float32(-np.inf))),
        start=jnp.asarray(padp(t.start)),
        count=jnp.asarray(padp(t.count)),
        order=jnp.asarray(padn(t.order)),
        root=jnp.asarray(root, jnp.int32),
    )
    if quant != "none":
        di = with_quant_replica(di, quant)
    return di


def with_quant_replica(di: DeviceIndex, quant: str) -> DeviceIndex:
    """Functional copy of ``di`` carrying the compressed corpus replica
    for ``quant`` (DESIGN.md §12). Pure jnp over the last two axes of
    ``vecs``, so it works on a plain (n, d) index and on the shard-
    stacked (S, n, d) form alike; ``quant="none"`` drops any replica."""
    from ..kernels.quant import QUANTS, quant_replica

    if quant == "none":
        return dataclasses.replace(di, qvecs=None, qscale=None)
    if quant not in QUANTS:
        raise ValueError(f"unknown quant {quant!r}; expected one of {QUANTS}")
    qvecs, qscale = quant_replica(di.vecs, quant)
    return dataclasses.replace(di, qvecs=qvecs, qscale=qscale)


@dataclasses.dataclass(frozen=True)
class SearchParams:
    """Static search configuration (hashable; becomes part of the jit key)."""

    k: int = 10
    ef: int = 64
    c_e: int = 10            # paper: k
    c_n: int = 32            # paper: M
    stack_cap: int = 64      # DFS stack depth bound (height + slack)
    max_steps: int = 4096    # RangeFilter pop budget (router="dfs" only)
    scan_budget: int = 64    # entry-scan window per candidate node
    max_hops: int = 0        # 0 => ef * 4 (generous; loop exits on its own)
    backend: str = "jnp"     # scoring backend, one of BACKENDS
    expand_width: int = 1    # frontier width E: pool entries expanded per hop
    router: str = "level"    # Phase-A tree router, one of ROUTERS
    strategy: str = "graph"  # execution strategy, one of STRATEGIES (§10)
    # "auto" dispatch threshold in absolute in-range-object units: scan
    # when the routing bound is <= this. 0 = derive from the index as
    # DEFAULT_SCAN_FRAC of the (total) corpus at Planner build time.
    scan_threshold: int = 0
    # level-sync frontier width bound (per level). 0 = derive from the
    # index (derive/validate_search_params fill it in; routing with 0
    # raises at trace time instead of silently dropping branches — no
    # fixed default is safe across index sizes, unlike stack_cap whose
    # height+1 bound is)
    frontier_cap: int = 0
    # quantized score path (DESIGN.md §12): which compressed replica the
    # scoring kernels stream, one of QUANTS. Non-"none" over-fetches
    # top-(k * rerank_mult) candidates on the replica, then reranks them
    # through the exact f32 gather_l2_filter path — final ids/dists are
    # f32-exact, bit-identical to the unquantized oracle whenever the
    # true top-k survives the over-fetch.
    quant: str = "none"
    rerank_mult: int = 4
    # "hybrid" per-node dispatch threshold in absolute object units: an
    # antichain node brute-scans as a contiguous DFS window iff its
    # subtree count is <= this. 0 = inherit the resolved scan_threshold
    # (so by default every lane "auto" would scan becomes a pure
    # windowed scan that visits only its in-range windows).
    node_scan_threshold: int = 0
    # predicate compiler (DESIGN.md §15): largest disjoint box cover a
    # compiled boolean filter expression may execute as before lowering
    # falls back to the dense row-bitmask brute scan. Each box costs one
    # full per-disjunct dispatch lane; the bitmask fallback costs one
    # exact f32 full-corpus pass regardless of strategy/quant.
    box_budget: int = 8

    def __post_init__(self):
        if self.expand_width < 1:
            raise ValueError(f"expand_width must be >= 1, "
                             f"got {self.expand_width}")
        if self.expand_width > self.ef:
            # the frontier can never hold more than ef candidates, and the
            # hop body's (E, H, M) gather assumes E selected slots exist
            raise ValueError(f"expand_width must be <= ef "
                             f"({self.ef}), got {self.expand_width}")
        if self.c_e > self.ef:
            # entry seeding writes pool slots [0:c_e) but the beam is only
            # ef wide — entries past it would be silently sealed by the
            # first merge (and the seed would over-mark tail slots that
            # pool_merge_tail expects sealed)
            raise ValueError(f"c_e must be <= ef ({self.ef}), got "
                             f"{self.c_e}: the entry seed writes the first "
                             f"c_e pool slots and the beam holds only ef")
        if self.router not in ROUTERS:
            raise ValueError(f"unknown router {self.router!r}; expected "
                             f"one of {ROUTERS}")
        if self.strategy not in STRATEGIES:
            raise ValueError(f"unknown strategy {self.strategy!r}; expected "
                             f"one of {STRATEGIES} (graph = tree-routed "
                             f"greedy search, scan = exact brute scan, "
                             f"auto = per-query planner dispatch)")
        if self.scan_threshold < 0:
            raise ValueError(f"scan_threshold must be >= 0 (0 = derive "
                             f"DEFAULT_SCAN_FRAC of the corpus from the "
                             f"index), got {self.scan_threshold}")
        if self.frontier_cap < 0:
            raise ValueError(f"frontier_cap must be >= 0 (0 = derive from "
                             f"the index), got {self.frontier_cap}")
        if self.quant not in QUANTS:
            raise ValueError(f"unknown quant {self.quant!r}; expected one "
                             f"of {QUANTS}")
        if self.rerank_mult < 1:
            raise ValueError(f"rerank_mult must be >= 1, "
                             f"got {self.rerank_mult}")
        if self.node_scan_threshold < 0:
            raise ValueError(f"node_scan_threshold must be >= 0 (0 = "
                             f"inherit scan_threshold), "
                             f"got {self.node_scan_threshold}")
        if self.box_budget < 1:
            raise ValueError(f"box_budget must be >= 1 (the smallest "
                             f"compiled predicate cover is one box), "
                             f"got {self.box_budget}")

    def hops(self) -> int:
        return self.max_hops or self.ef * 4


# --------------------------------------------------------------------------
# Parameter validation against a concrete index
# --------------------------------------------------------------------------
#
# Three SearchParams fields bound fixed-shape buffers whose sufficiency
# depends on the *index*, not the query: an undersized ``stack_cap``
# silently drops DFS branches at the overflow clamp, an undersized
# ``frontier_cap`` does the same to the level-sync router's per-level
# frontier, and an undersized ``scan_budget`` makes the entry scan return
# -1 for a scannable node whose first in-range object sits past the window
# — all degrade recall with no error. The helpers below derive the exact
# sufficient values from a DeviceIndex so callers can refuse (``"raise"``)
# or auto-raise (``"adjust"``) undersized params instead of silently
# missing entries.

def _di_height(di: "DeviceIndex") -> int:
    """Tree height for a plain (n, H, M) or shard-stacked (S, n, H, M)
    DeviceIndex."""
    return int(di.nbrs.shape[-2])


def required_stack_cap(di: "DeviceIndex") -> int:
    """DFS depth bound: one pending sibling per level plus the current node."""
    return _di_height(di) + 1


def required_scan_budget(di: "DeviceIndex") -> int:
    """Smallest scan window that can never silently miss an entry.

    Entry scans can *fail partway* only on nodes where membership does not
    imply predicate satisfaction: leaves (the §6 leaf fallback scans them
    under partial D) and nodes with blacklisted dims (D reaches full without
    rectangle containment on BL dims). A covered node with BL == 0 is
    genuinely contained, so its first object always matches and any budget
    suffices. The max object count over the scannable set is therefore
    exact: at this budget the windowed scan equals the reference's
    full-node scan.
    """
    left = np.asarray(jax.device_get(di.left)).ravel()
    bl = np.asarray(jax.device_get(di.bl)).ravel()
    count = np.asarray(jax.device_get(di.count)).ravel()
    scannable = (left < 0) | (bl != 0)
    return int(count[scannable].max()) if scannable.any() else 1


def derive_search_params(p: SearchParams, di: "DeviceIndex") -> SearchParams:
    """Copy of ``p`` with scan_budget/stack_cap/frontier_cap raised (never
    lowered) to the sufficient values for ``di``."""
    return dataclasses.replace(
        p,
        scan_budget=max(p.scan_budget, required_scan_budget(di)),
        stack_cap=max(p.stack_cap, required_stack_cap(di)),
        frontier_cap=(max(p.frontier_cap, required_frontier_cap(di))
                      if p.router == "level" else p.frontier_cap),
    )


def _check_strategy_combo(p: SearchParams) -> None:
    """Reject strategy combinations that cannot execute (DESIGN.md §10) —
    checked by every runtime entry point via validate_search_params, with
    actionable messages (satellite contract, tests/test_planner.py)."""
    if p.strategy in ("scan", "auto", "hybrid") \
            and p.backend not in SCAN_BACKENDS:
        unfused = [b for b in BACKENDS if b not in SCAN_BACKENDS]
        raise ValueError(
            f"strategy={p.strategy!r} is incompatible with backend "
            f"{p.backend!r}: the brute-scan path masks the pass with the "
            f"range predicate, which needs the fused filter kernel "
            f"('pallas_gather_l2_filter') or the jnp mask oracle ('jnp'); "
            f"the unfused pallas backends {unfused} have no filter form. "
            f"Switch backend, or force strategy='graph'.")
    if p.strategy in ("auto", "hybrid") and p.router != "level":
        raise ValueError(
            f"strategy={p.strategy!r} requires router='level' (got "
            f"{p.router!r}): the DFS router early-stops after c_e entries "
            f"and never sweeps the full scannable antichain, so its "
            f"subtree-count sum is not an in-range cardinality bound and "
            f"its visited node set is not the full antichain "
            f"(core/router.py). Use router='level', or pick the strategy "
            f"explicitly.")
    if p.quant != "none" and p.backend not in SCAN_BACKENDS:
        unfused = [b for b in BACKENDS if b not in SCAN_BACKENDS]
        raise ValueError(
            f"quant={p.quant!r} is incompatible with backend "
            f"{p.backend!r}: the quantized score path needs the fused "
            f"filter kernel ('pallas_gather_l2_filter' — which has bf16 "
            f"and int8 replica forms) or the jnp oracle ('jnp'); the "
            f"unfused pallas backends {unfused} have no replica form. "
            f"Switch backend, or set quant='none'.")


def validate_search_params(p: SearchParams, di: "DeviceIndex", *,
                           on_undersized: str = "raise",
                           expr=None) -> SearchParams:
    """Check ``p``'s index-dependent buffer bounds against ``di``, plus the
    strategy/backend/router compatibility rules (``_check_strategy_combo``
    — those raise regardless of ``on_undersized``; they are contract
    violations, not sizing choices).

    ``expr``: optional predicate expression (core/predicate.py) to
    validate against this index's attribute count — malformed ASTs are
    rejected here, at params-validation time, with actionable messages
    naming the bad node's path (DESIGN.md §15).

    on_undersized: ``"raise"`` (error with the sufficient values),
    ``"adjust"`` (return an auto-raised copy), or ``"ignore"`` (legacy
    silent-truncation behavior, for callers that deliberately trade recall
    for a smaller scan window).
    """
    _check_strategy_combo(p)
    if expr is not None:
        from .predicate import validate_expr
        validate_expr(expr, int(di.attrs.shape[-1]))
    if on_undersized == "ignore":
        return p
    if on_undersized not in ("raise", "adjust"):
        raise ValueError(f"on_undersized must be raise|adjust|ignore, "
                         f"got {on_undersized!r}")
    need_scan = required_scan_budget(di)
    need_stack = required_stack_cap(di)
    # the frontier bound only backs the level-sync router's buffers
    need_front = required_frontier_cap(di) if p.router == "level" else 0
    if (p.scan_budget >= need_scan and p.stack_cap >= need_stack
            and p.frontier_cap >= need_front):
        return p
    if on_undersized == "adjust":
        return dataclasses.replace(
            p, scan_budget=max(p.scan_budget, need_scan),
            stack_cap=max(p.stack_cap, need_stack),
            frontier_cap=max(p.frontier_cap, need_front))
    raise ValueError(
        f"SearchParams undersized for this index: need scan_budget >= "
        f"{need_scan} (got {p.scan_budget}), stack_cap >= {need_stack} "
        f"(got {p.stack_cap}) and frontier_cap >= {need_front} (got "
        f"{p.frontier_cap}); an undersized scan_budget silently returns "
        f"-1 entries for large scannable nodes, and an undersized "
        f"frontier_cap silently drops level-sync router branches. Use "
        f"derive_search_params() or pass on_undersized='adjust'.")


# --------------------------------------------------------------------------
# Algorithms 2+3: greedy search with on-the-fly neighbor reconstruction
# (Algorithm 1 — Phase A routing — lives in core.router)
# --------------------------------------------------------------------------

def _dist_jnp(q: jax.Array, cand: jax.Array) -> jax.Array:
    # subtract/square in the CORPUS dtype (downcasting q — a (d,) vector),
    # accumulating the reduction in f32 via the reduce's accumulator rather
    # than a standalone convert: an explicit upcast of the gathered rows
    # gets algebraically hoisted above the gather into a full-corpus f32
    # convert (observed: +25% HBM term and +1.4 GiB peak in the bf16 §Perf
    # iteration).
    diff = cand - q.astype(cand.dtype)[None, :]
    return jnp.sum(diff * diff, axis=-1, dtype=jnp.float32)


# Every backend implements fn(vecs (n, d), q (d,), safe_ids (C,) int32)
# -> (C,) f32; ids are pre-clamped in-range by the caller (invalid slots get
# their distances overwritten with inf upstream, so garbage rows are fine).

def _dist_ids_jnp(vecs, q, ids):
    return _dist_jnp(q, vecs[ids])


def _dist_ids_pallas_l2(vecs, q, ids, *, interpret):
    from ..kernels.l2dist import l2dist_qc_raw

    rows = vecs[ids]                              # materialized gather
    C, d = rows.shape
    tc = min(128, _ceil_mult(C, 8))
    td = min(128, _ceil_mult(d, 8))
    rp = _pad2(rows, _ceil_mult(C, tc), _ceil_mult(d, td))
    qp = jnp.pad(q.astype(rows.dtype), (0, rp.shape[1] - d))[None]
    out = l2dist_qc_raw(qp, rp[None], tb=1, tc=tc, td=td, interpret=interpret)
    return out[0, :C]


def _dist_ids_gather_l2(vecs, q, ids, *, interpret):
    # blocked production form: C_BLK candidate rows per grid step, one
    # vectorized tile reduction (bitwise-equal to the row-per-step
    # gather_l2_raw — tests/test_kernels.py pins it)
    from ..kernels.gather_l2 import gather_l2_blocked_raw

    return gather_l2_blocked_raw(ids[None], vecs, q[None].astype(vecs.dtype),
                                 interpret=interpret)[0]


def _ceil_mult(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad2(x, r, c):
    return jnp.pad(x, ((0, r - x.shape[0]), (0, c - x.shape[1])))


def resolve_dist_ids(backend: Optional[str] = None, *,
                     dist_fn: Optional[Callable] = None,
                     interpret: Optional[bool] = None) -> Callable:
    """Resolve an *unfused* distance backend to the legacy
    ``fn(vecs, q, ids)`` contract. ``dist_fn`` (legacy ``fn(q, rows)``
    signature) wins if given; ``interpret=None`` auto-selects by JAX
    backend (Mosaic on TPU, interpreter elsewhere). Predicate-fused
    backends have no dist-only form — resolve them via
    ``resolve_scorer`` (the engine-facing registry)."""
    if dist_fn is not None:
        return lambda vecs, q, ids: dist_fn(q, vecs[ids])
    backend = backend or "jnp"
    if backend == "jnp":
        return _dist_ids_jnp
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if backend == "pallas_l2":
        return functools.partial(_dist_ids_pallas_l2, interpret=interpret)
    if backend == "pallas_gather_l2":
        return functools.partial(_dist_ids_gather_l2, interpret=interpret)
    if backend == "pallas_gather_l2_filter":
        raise ValueError(
            f"{backend!r} is predicate-fused and has no dist-only form; "
            f"resolve it with resolve_scorer()")
    raise ValueError(f"unknown distance backend {backend!r}; "
                     f"expected one of {BACKENDS}")


# --------------------------------------------------------------------------
# Scorer registry (DESIGN.md §9) — Phase B's pluggable scoring contract
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Scorer:
    """One scoring backend behind one contract.

    ``score(di, q, qlo, qhi, ids) -> (C,) f32``: exact squared L2 for
    valid lanes, ``+inf`` for ``-1`` (pad/invalid) lanes — scorers with
    ``fused_filter=True`` additionally emit ``+inf`` for lanes whose
    attribute row falls outside ``[qlo, qhi]`` (the in-kernel predicate;
    for the engine's candidate buffers, which are in-range by
    construction, this is defense in depth at the cost of an m-float DMA
    per row). ``in_range`` is the stream-side predicate the hop budget
    consumes (Alg. 2's early-exit counts *in-range* appends, so the
    predicate must be known for the whole fused stream before the c_n
    compaction — DESIGN.md §9 spells out why it cannot move into the
    compacted scoring call without changing results).
    """

    name: str
    fused_filter: bool
    score: Callable  # (di, q, qlo, qhi, ids (C,) i32) -> (C,) f32

    def in_range(self, di: "DeviceIndex", qlo: jax.Array, qhi: jax.Array,
                 ids: jax.Array) -> jax.Array:
        """Predicate over pre-clamped ids: (C,) bool (garbage rows allowed
        — callers AND with their validity mask)."""
        a = di.attrs[ids]
        return jnp.all((a >= qlo) & (a <= qhi), axis=-1)


def _unfused_scorer(name: str, dist_ids: Callable) -> Scorer:
    def score(di, q, qlo, qhi, ids):
        safe = jnp.maximum(ids, 0)
        d = dist_ids(di.vecs, q, safe)
        return jnp.where(ids >= 0, d, jnp.float32(jnp.inf))
    return Scorer(name=name, fused_filter=False, score=score)


def _filter_scorer(interpret: bool) -> Scorer:
    from ..kernels.gather_l2_filter import gather_l2_filter_blocked_raw

    def score(di, q, qlo, qhi, ids):
        # the kernel consumes -1 lanes natively (emits +inf), so there is
        # no caller-side clamp or validity overwrite here
        return gather_l2_filter_blocked_raw(
            ids[None], di.vecs, di.attrs, q[None].astype(di.vecs.dtype),
            qlo[None], qhi[None], interpret=interpret)[0]
    return Scorer(name="pallas_gather_l2_filter", fused_filter=True,
                  score=score)


def _quant_scorer(backend: str, quant: str, interpret: bool) -> Scorer:
    """Scorer over the compressed replica (DESIGN.md §12): distances come
    from ``di.qvecs`` (dequantized in-kernel / in-oracle), the predicate
    from the exact f32 ``di.attrs`` as always. Quantized distances are
    approximate — the engine reranks the over-fetched top candidates
    through the exact f32 path before answering."""
    if backend == "pallas_gather_l2_filter":
        if quant == "bf16":
            from ..kernels.gather_l2_filter import \
                gather_l2_filter_blocked_raw

            def score(di, q, qlo, qhi, ids):
                # dtype-generic kernel: the bf16 replica streams directly
                return gather_l2_filter_blocked_raw(
                    ids[None], di.qvecs, di.attrs,
                    q[None].astype(di.qvecs.dtype), qlo[None], qhi[None],
                    interpret=interpret)[0]
        else:
            from ..kernels.gather_l2_filter import \
                gather_l2_filter_q8_blocked_raw

            def score(di, q, qlo, qhi, ids):
                return gather_l2_filter_q8_blocked_raw(
                    ids[None], di.qvecs, di.qscale, di.attrs, q[None],
                    qlo[None], qhi[None], interpret=interpret)[0]
    else:                                        # jnp oracle forms
        if quant == "bf16":
            from ..kernels.ref import gather_l2_filter_ref

            def score(di, q, qlo, qhi, ids):
                return gather_l2_filter_ref(ids[None], di.qvecs, di.attrs,
                                            q[None], qlo[None], qhi[None])[0]
        else:
            from ..kernels.ref import gather_l2_filter_q8_ref

            def score(di, q, qlo, qhi, ids):
                return gather_l2_filter_q8_ref(
                    ids[None], di.qvecs, di.qscale, di.attrs, q[None],
                    qlo[None], qhi[None])[0]
    return Scorer(name=f"{backend}+{quant}", fused_filter=True, score=score)


def resolve_scorer(backend: Optional[str] = None, *,
                   dist_fn: Optional[Callable] = None,
                   interpret: Optional[bool] = None,
                   quant: str = "none") -> Scorer:
    """Resolve ``SearchParams.backend`` to a ``Scorer``. A legacy
    ``dist_fn(q, rows)`` override wins if given (wrapped as an unfused
    scorer); ``interpret=None`` auto-selects by JAX backend. With
    ``quant`` != "none" the scorer streams the compressed replica
    (``di.qvecs``/``di.qscale`` — DESIGN.md §12) and its distances are
    approximate; pair it with the exact scorer for the rerank tail (see
    ``resolve_scorer_pair``)."""
    if dist_fn is not None:
        if quant != "none":
            raise ValueError("dist_fn overrides cannot run on the "
                             "quantized replica; set quant='none'")
        return _unfused_scorer("dist_fn", resolve_dist_ids(dist_fn=dist_fn))
    backend = backend or "jnp"
    if backend not in BACKENDS:
        raise ValueError(f"unknown scoring backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    if quant not in QUANTS:
        raise ValueError(f"unknown quant {quant!r}; expected one of {QUANTS}")
    if quant != "none":
        if backend not in SCAN_BACKENDS:
            raise ValueError(f"quant={quant!r} requires a backend in "
                             f"{SCAN_BACKENDS}, got {backend!r}")
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return _quant_scorer(backend, quant, interpret)
    if backend == "pallas_gather_l2_filter":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return _filter_scorer(interpret)
    return _unfused_scorer(
        backend, resolve_dist_ids(backend, interpret=interpret))


def resolve_scorer_pair(p: "SearchParams", *,
                        dist_fn: Optional[Callable] = None,
                        interpret: Optional[bool] = None
                        ) -> tuple[Scorer, Optional[Scorer]]:
    """(loop scorer, exact rerank scorer) for ``p`` (DESIGN.md §12).

    quant="none": (exact scorer, None) — no rerank tail. Otherwise the
    loop scorer streams the compressed replica and the second element is
    the exact f32 scorer the rerank tail rescores the over-fetched
    candidates with."""
    if p.quant == "none":
        return resolve_scorer(p.backend, dist_fn=dist_fn,
                              interpret=interpret), None
    quant_scorer = resolve_scorer(p.backend, dist_fn=dist_fn,
                                  interpret=interpret, quant=p.quant)
    exact = resolve_scorer(p.backend, interpret=interpret)
    return quant_scorer, exact


def _lex_topk(ids: jax.Array, dists: jax.Array,
              k: int) -> tuple[jax.Array, jax.Array]:
    """Top-k of (dists, ids) under the (dist, id) lexicographic contract:
    ascending distance, ties to the lowest id, -1/+inf pad lanes sort
    last (ids rewrite to -1 wherever the kept distance is +inf). Works
    on (..., C) batches; C >= k required."""
    key_id = jnp.where(ids >= 0, ids, jnp.int32(np.iinfo(np.int32).max))
    sel = jnp.lexsort((key_id, dists), axis=-1)[..., :k]
    d = jnp.take_along_axis(dists, sel, axis=-1)
    i = jnp.take_along_axis(ids, sel, axis=-1)
    return jnp.where(jnp.isinf(d), -1, i), d


def _query_one(di: DeviceIndex, q: jax.Array, qlo: jax.Array, qhi: jax.Array,
               p: SearchParams, scorer: Scorer,
               exact_scorer: Optional[Scorer] = None
               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    n = di.n
    H, M = di.nbrs.shape[1], di.nbrs.shape[2]
    HM = H * M
    E = p.expand_width
    L = E * HM                               # fused candidate stream length

    # Phase A: tree routing (level-sync sweep or legacy DFS — core.router);
    # the card byproduct is the planner's signal (§10) — unused in-graph
    entries, _ = resolve_router(p.router)(di, qlo, qhi, p)
    e_valid = entries >= 0
    e_dist = scorer.score(di, q, qlo, qhi, entries)

    visited = beam.visited_init(n)
    visited = beam.visited_mark(visited, entries, e_valid)

    # sorted pool (beam substrate): beam [0:ef] + scratch tail of E*c_n slots
    pool0 = beam.pool_seed(p.ef + E * p.c_n, entries, e_dist, e_valid)
    # intra-hop first-occurrence scratch: seen[i] holds the hop-tagged
    # stream position of id i's latest occurrence (see dedup note in body)
    seen0 = jnp.full((n,), -1, jnp.int32)

    def cond(st):
        pool, visited, seen, hops = st
        return beam.pool_frontier_alive(pool, p.ef) & (hops < p.hops())

    def body(st):
        pool, visited, seen, hops = st
        # -------- wide frontier: top-E unexpanded, closest first
        u_slots, us, uvalid = beam.pool_top_unexpanded(pool, p.ef, E)
        pool = beam.pool_mark_expanded_many(pool, u_slots, uvalid)

        # -------- ReconsNbr (Alg. 2) over the fused E*H*M candidate stream,
        # with exact per-expansion budget semantics
        u_safe = jnp.where(uvalid, us, 0)
        rows = di.nbrs[u_safe]                  # (E, H, M) — one gather
        nid = rows.reshape(L)
        valid = ((rows >= 0) & uvalid[:, None, None]).reshape(L)
        nid_safe = jnp.where(valid, nid, 0)

        # intra-stream dedup: the sequential scan marks-then-skips, so only
        # an id's first occurrence (expansion-major, level order) counts.
        # Scatter-based first-occurrence mark, O(L) instead of the former
        # O(L log L) argsort: every lane scatter-maxes a hop-tagged key that
        # DECREASES along the stream, so after the scatter an id's slot
        # holds its earliest occurrence this hop; keys grow by L per hop,
        # which makes stale entries lose every future max without an O(n)
        # reset. A lane is first iff it reads its own key back.
        pos = jnp.arange(L, dtype=jnp.int32)
        tag = hops * L + (L - 1 - pos)
        seen = seen.at[jnp.where(valid, nid, n)].max(tag, mode="drop")
        is_first = valid & (seen[nid_safe] == tag)

        fresh = is_first & ~visited[nid_safe]
        in_range = valid & scorer.in_range(di, qlo, qhi, nid_safe)
        append = fresh & in_range
        # per-expansion budget: each of the E expanded candidates scans its
        # own HM segment under its own c_n window (segmented excl. cumsum)
        seg = append.reshape(E, HM)
        napp_excl = (jnp.cumsum(seg, axis=1) - seg).reshape(L)
        scanned = napp_excl < p.c_n             # scan alive when reaching j
        visited = beam.visited_mark(visited, nid, fresh & scanned)
        keep = append & scanned
        # compact kept ids into E*c_n slots (segment-major)
        base = jnp.repeat(jnp.arange(E, dtype=jnp.int32) * p.c_n, HM)
        slots = jnp.where(keep, base + napp_excl, E * p.c_n)
        buf = jnp.full((E * p.c_n,), -1,
                       jnp.int32).at[slots].set(nid, mode="drop")

        # -------- ONE scoring call over all E expansions' survivors (the
        # scorer owns pad-lane +inf; fused scorers re-check the predicate
        # in-kernel — a no-op here, the buffer is in-range by construction)
        bvalid = buf >= 0
        bd = scorer.score(di, q, qlo, qhi, buf)

        # -------- pool merge (Alg. 3 lines 10-13)
        pool = beam.pool_merge_tail(pool, p.ef, buf, bd, bvalid)
        return pool, visited, seen, hops + 1

    pool, visited, seen, hops = jax.lax.while_loop(
        cond, body, (pool0, visited, seen0, jnp.int32(0)))
    if exact_scorer is None:
        return pool.ids[: p.k], pool.dists[: p.k], hops
    # quantized rerank tail (DESIGN.md §12): the loop above ranked the
    # pool on compressed-replica distances, so the quantized order near
    # the k boundary may invert vs f32. Rescore the top
    # min(ef, k * rerank_mult) pool entries through the exact f32 path
    # and take the (dist, id)-lexicographic top-k — a static python
    # branch, so quant="none" programs are untouched.
    rr = max(p.k, min(p.ef, p.k * p.rerank_mult))
    cand = pool.ids[:rr]
    exact_d = exact_scorer.score(di, q, qlo, qhi, cand)
    ids_k, dists_k = _lex_topk(cand, exact_d, p.k)
    return ids_k, dists_k, hops


def make_search_fn(p: SearchParams, *, dist_fn=None, donate: bool = False,
                   di: Optional[DeviceIndex] = None,
                   on_undersized: str = "raise"):
    """Builds jit(search)(di, queries (B,d), qlo (B,m), qhi (B,m)) ->
    (ids (B,k) int32, dists (B,k) f32, hops (B,) int32).

    The scoring backend comes from ``p.backend`` unless a legacy
    ``dist_fn(q, rows)`` override is supplied. Pass the target ``di`` to
    validate the index-dependent buffer bounds (scan_budget / stack_cap /
    frontier_cap) up front: by default an undersized configuration raises
    instead of silently returning -1 entries (``on_undersized`` selects
    raise/adjust/ignore — see ``validate_search_params``)."""
    if p.strategy != "graph":
        raise ValueError(
            f"make_search_fn builds the jitted graph program only; "
            f"strategy={p.strategy!r} dispatches per query on the host — "
            f"build an engine.Planner (or call search_batch, which does).")
    if di is not None:
        p = validate_search_params(p, di, on_undersized=on_undersized)
    scorer, exact = resolve_scorer_pair(p, dist_fn=dist_fn)

    @functools.partial(jax.jit, static_argnames=())
    def search(di: DeviceIndex, queries, qlo, qhi):
        fn = functools.partial(_query_one, p=p, scorer=scorer,
                               exact_scorer=exact)
        return jax.vmap(lambda q, lo, hi: fn(di, q, lo, hi))(queries, qlo, qhi)

    return search


def search_batch(index_or_di, queries: np.ndarray, preds, params: SearchParams,
                 *, dist_fn=None, on_undersized: str = "adjust"):
    """Convenience host API: accepts a host KHIIndex or a DeviceIndex plus a
    list of ``Predicate``s; returns numpy (ids, dists, hops).

    Index-dependent buffer bounds are auto-raised by default (the derived
    scan_budget makes the windowed entry scan exact — DESIGN.md §6).
    ``params.strategy`` other than ``"graph"`` routes through a Planner
    (DESIGN.md §10): ``"scan"`` answers every query with the exact brute
    scan (hops = 0), ``"auto"`` dispatches per query on the routing
    bound."""
    di = index_or_di
    if isinstance(di, KHIIndex):
        di = device_put_index(di)
    qlo = np.stack([pr.lo for pr in preds]).astype(np.float32)
    qhi = np.stack([pr.hi for pr in preds]).astype(np.float32)
    if params.strategy != "graph":
        planner = Planner(di, params, dist_fn=dist_fn,
                          on_undersized=on_undersized)
        ids, dists, hops, _ = planner.search(queries, qlo, qhi)
        return ids, dists, hops
    fn = make_search_fn(params, dist_fn=dist_fn, di=di,
                        on_undersized=on_undersized)
    ids, dists, hops = fn(di, jnp.asarray(queries), jnp.asarray(qlo),
                          jnp.asarray(qhi))
    return np.asarray(ids), np.asarray(dists), np.asarray(hops)


# --------------------------------------------------------------------------
# Selectivity-adaptive query planner (DESIGN.md §10)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "w_cap", "use_kernel",
                                             "interpret"))
def _windows_one(pos_vecs, pos_attrs, order, q, qlo, qhi, starts, counts,
                 *, k: int, w_cap: int, use_kernel: bool, interpret: bool):
    """One shard's windowed scan (DESIGN.md §12): positions from the
    kernel (or jnp oracle on backend='jnp') map back through the DFS
    ``order`` permutation to local row ids."""
    if use_kernel:
        from ..kernels.scan_topk import scan_topk_windows_raw
        pos, dd = scan_topk_windows_raw(pos_vecs, pos_attrs, q, qlo, qhi,
                                        starts, counts, k=k, w_cap=w_cap,
                                        interpret=interpret)
    else:
        from ..kernels.ref import scan_topk_windows_ref
        pos, dd = scan_topk_windows_ref(pos_vecs, pos_attrs, q, qlo, qhi,
                                        starts, counts, k)
    ids = jnp.where(pos >= 0, order[jnp.maximum(pos, 0)], -1)
    return ids, dd


@functools.partial(jax.jit, static_argnames=("k", "w_cap", "use_kernel",
                                             "interpret"))
def _windows_sharded(pos_vecs, pos_attrs, order, offsets, q, qlo, qhi,
                     starts, counts, *, k: int, w_cap: int,
                     use_kernel: bool, interpret: bool):
    """Static unroll over shards (starts/counts (S, B, W)), local ids to
    global, merge-k — the same shard fan-out shape as the scan path."""
    from .sharded import _local_to_global, _merge_topk
    S = pos_vecs.shape[0]
    gi, gd = [], []
    for s in range(S):
        ids, dd = _windows_one(pos_vecs[s], pos_attrs[s], order[s], q, qlo,
                               qhi, starts[s], counts[s], k=k, w_cap=w_cap,
                               use_kernel=use_kernel, interpret=interpret)
        gids = _local_to_global(ids, offsets[s], S)
        gi.append(gids)
        gd.append(jnp.where(gids >= 0, dd, jnp.inf))
    return _merge_topk(jnp.stack(gi), jnp.stack(gd), k)


def _scan_exact(vecs, attrs_nan, q, qlo, qhi, k: int, *,
                use_kernel: bool, interpret: bool):
    """One shard's exact predicate-fused brute scan (DESIGN.md §10):
    the Pallas kernel or the jnp oracle, shared by the host Planner and
    the collective shard_map program (§14)."""
    if use_kernel:
        from ..kernels.scan_topk import scan_topk_raw
        return scan_topk_raw(vecs, attrs_nan, q, qlo, qhi, k=k,
                             interpret=interpret)
    from ..kernels.ref import scan_topk_ref
    return scan_topk_ref(vecs, attrs_nan, q, qlo, qhi, k)


def _scan_shard_topk(di: "DeviceIndex", shard, attrs_nan, q, qlo, qhi,
                     p: "SearchParams", *, use_kernel: bool,
                     interpret: bool):
    """One shard's scan-path top-k under every quant tier (DESIGN.md
    §10/§12) — the device half of the Planner's scan program, extracted
    so the in-collective pipeline (§14) runs the bit-identical per-shard
    scan inside shard_map. ``shard`` indexes a stacked (S, ...) index;
    pass None for an already-squeezed single-shard DeviceIndex."""
    quant = p.quant
    vecs = di.vecs if shard is None else di.vecs[shard]
    if quant == "none":
        return _scan_exact(vecs, attrs_nan, q, qlo, qhi, p.k,
                           use_kernel=use_kernel, interpret=interpret)
    # quantized scan + exact rerank (§12): over-fetch the top
    # k * rerank_mult on the compressed replica, rescore those
    # candidates on the f32 corpus through the gather path, and
    # take the (dist, id)-lexicographic top-k — exact whenever
    # the true top-k survives the over-fetch
    qvecs = di.qvecs if shard is None else di.qvecs[shard]
    kq = min(max(p.k, p.k * p.rerank_mult), vecs.shape[0])
    if quant == "bf16":
        cids, _ = _scan_exact(qvecs, attrs_nan, q, qlo, qhi, kq,
                              use_kernel=use_kernel, interpret=interpret)
    elif use_kernel:
        from ..kernels.scan_topk import scan_topk_q8_raw
        qscale = di.qscale if shard is None else di.qscale[shard]
        cids, _ = scan_topk_q8_raw(qvecs, qscale, attrs_nan, q,
                                   qlo, qhi, k=kq, interpret=interpret)
    else:
        from ..kernels.ref import scan_topk_q8_ref
        qscale = di.qscale if shard is None else di.qscale[shard]
        cids, _ = scan_topk_q8_ref(qvecs, qscale, attrs_nan, q,
                                   qlo, qhi, kq)
    if use_kernel:
        from ..kernels.gather_l2_filter import \
            gather_l2_filter_blocked_raw
        exact_d = gather_l2_filter_blocked_raw(
            cids, vecs, attrs_nan, q, qlo, qhi, interpret=interpret)
    else:
        from ..kernels.ref import gather_l2_filter_ref
        exact_d = gather_l2_filter_ref(cids, vecs, attrs_nan, q,
                                       qlo, qhi)
    return _lex_topk(cids, exact_d, p.k)


def _merge_dedup(ids_a: np.ndarray, d_a: np.ndarray, ids_b: np.ndarray,
                 d_b: np.ndarray, k: int,
                 out_dtype=np.int32) -> tuple[np.ndarray, np.ndarray]:
    """Merge two partial top-k streams under the (dist, id) lexicographic
    contract with id-level dedup (DESIGN.md §12): a row found by BOTH the
    graph walk and a window keeps its best (lowest) distance — the two
    paths may disagree by f32 reduce-order ulps, and without dedup a
    twice-found row could crowd a genuinely distinct k-th neighbor out.
    Two lexsort passes: group by id keeping the best occurrence first,
    mask the rest to (+inf, -1), then rank by (dist, id) and take k.
    ``out_dtype=np.int64`` preserves external streaming ids (DESIGN.md
    §11/§15 — the predicate compiler's cross-disjunct merge under a live
    delta segment); all comparisons run in int64 either way."""
    ids = np.concatenate([ids_a, ids_b], axis=1).astype(np.int64)
    d = np.concatenate([d_a, d_b], axis=1).astype(np.float32)
    sentinel = np.iinfo(np.int64).max
    key = np.where(ids >= 0, ids, sentinel)
    o1 = np.lexsort((d, key), axis=-1)            # id-major, best dist first
    key = np.take_along_axis(key, o1, axis=1)
    d = np.take_along_axis(d, o1, axis=1)
    dup = np.zeros_like(key, bool)
    dup[:, 1:] = (key[:, 1:] == key[:, :-1]) & (key[:, 1:] != sentinel)
    d = np.where(dup, np.inf, d)
    key = np.where(dup, sentinel, key)
    o2 = np.lexsort((key, d), axis=-1)[:, :k]     # (dist, id) rank, take k
    out_d = np.take_along_axis(d, o2, axis=1).astype(np.float32)
    out_i = np.take_along_axis(key, o2, axis=1)
    out_i = np.where(np.isinf(out_d), -1, out_i).astype(out_dtype)
    return out_i, out_d


def _mask_scan_one(vecs, mask, q, k: int, *, use_kernel: bool,
                   interpret: bool):
    """One shard's bitmask-fused exact brute scan (DESIGN.md §15) — the
    predicate compiler's dense-fallback execution: the Pallas mask kernel
    or its jnp oracle, always on the f32 corpus (the fallback trades the
    quantized replica for unconditional exactness)."""
    if use_kernel:
        from ..kernels.scan_topk import scan_topk_mask_raw
        return scan_topk_mask_raw(vecs, mask, q, k=k, interpret=interpret)
    from ..kernels.ref import scan_topk_mask_ref
    return scan_topk_mask_ref(vecs, mask, q, k)


def _merge_dedup_jnp(ids_a, d_a, ids_b, d_b, k: int):
    """Device twin of ``_merge_dedup`` for the in-collective hybrid path
    (DESIGN.md §14): the same two stable lexsort passes on device arrays
    — pinned bit-identical against the numpy form by tests. Global ids
    fit int32, so the sentinel is i32max (the numpy form's i64 widening
    changes no comparison)."""
    ids = jnp.concatenate([ids_a, ids_b], axis=1)
    d = jnp.concatenate([d_a, d_b], axis=1).astype(jnp.float32)
    sentinel = jnp.int32(np.iinfo(np.int32).max)
    key = jnp.where(ids >= 0, ids, sentinel)
    o1 = jnp.lexsort((d, key), axis=-1)           # id-major, best dist first
    key = jnp.take_along_axis(key, o1, axis=1)
    d = jnp.take_along_axis(d, o1, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros_like(key[:, :1], bool),
         (key[:, 1:] == key[:, :-1]) & (key[:, 1:] != sentinel)], axis=1)
    d = jnp.where(dup, jnp.inf, d)
    key = jnp.where(dup, sentinel, key)
    o2 = jnp.lexsort((key, d), axis=-1)[:, :k]    # (dist, id) rank, take k
    out_d = jnp.take_along_axis(d, o2, axis=1)
    out_i = jnp.take_along_axis(key, o2, axis=1)
    return jnp.where(jnp.isinf(out_d), -1, out_i).astype(jnp.int32), out_d


@dataclasses.dataclass
class Plan:
    """Host-side record of one batch's dispatch decisions.

    ``card`` is the Phase-A routing sweep's in-range cardinality bound
    per query (-1 when the strategy was forced and no estimate ran);
    ``use_scan`` the per-query dispatch; ``threshold`` the resolved
    absolute dispatch threshold (SearchParams.scan_threshold, or the
    DEFAULT_SCAN_FRAC derivation when that was 0).

    ``strategy="hybrid"`` (DESIGN.md §12) additionally records the
    per-NODE decision: ``mode`` is 0 = graph lane, 1 = pure-window lane
    (every antichain node small — answered exactly by the windowed
    scan, hops 0; these lanes also set ``use_scan``), 2 = mixed lane
    (graph walk + windows over the small nodes, streams merged);
    ``small_nodes`` holds one (B, P) bool mask per shard (antichain ∩
    count <= node_threshold — the windows' node set) and ``n_windows``
    the per-lane total across shards."""

    card: np.ndarray       # (B,) int64/int32
    use_scan: np.ndarray   # (B,) bool
    threshold: int
    node_threshold: int = 0
    mode: Optional[np.ndarray] = None         # (B,) int8, hybrid only
    n_windows: Optional[np.ndarray] = None    # (B,) int64, hybrid only
    small_nodes: Optional[list] = None        # per-shard (B, P) bool


@dataclasses.dataclass
class PredicatePlan:
    """Host-side record of one compiled-predicate batch (DESIGN.md §15).

    ``mode`` mirrors the program's: ``"boxes"`` executed the disjoint
    cover — one full per-disjunct strategy dispatch per box, recorded in
    ``box_plans`` (one ``Plan`` per box, in cover order) — while
    ``"bitmask"`` ran the dense fallback scan (``box_plans`` empty).
    ``lanes`` counts dispatched (query × disjunct) lanes per execution
    strategy — ``{"graph", "scan", "window"}``; mixed hybrid lanes count
    under both graph and window — the observability contract the serving
    snapshot exposes (the per-strategy lane-count satellite)."""

    mode: str
    n_boxes: int
    lanes: dict
    box_plans: list
    program: Any = None    # the compiled PredicateProgram


class Planner:
    """Per-query strategy dispatch over one (sharded) index (DESIGN.md §10).

    Two device programs and one host estimator behind one front door:

      * **plan** (``strategy="auto"`` only) — the routing cardinality
        bound: per query, the sum of subtree counts over the scanned
        KD-antichain, an upper bound on |O_B| that is exact on contained
        nodes; summed across shards for a ``ShardedKHI``. Evaluated by
        the node-parallel ``router.HostCardEstimator`` (the dispatch
        decision is host-side even in TPU serving; the device sweep
        ``route_level_card`` computes the identical quantity — pinned)
        behind a per-query **plan cache** keyed on the range-box bytes
        (plus a caller-supplied ``plan_salt`` naming the estimator
        state), so repeated boxes (faceted search, dashboard refreshes,
        the bench's steady state) re-dispatch without re-estimating.
        Pass ``plan_cache=`` to share one cache across planners whose
        estimator state is identical — the serving layer's degradation
        tiers (DESIGN.md §13) all dispatch off one cache this way.
      * **graph** — the two-phase wide-frontier engine (``_query_one``),
        vmapped; for a sharded index the same fan-out + O(S·k) merge the
        serving layer uses, with per-query hops = max over shards (the
        lockstep cost a vmapped shard pays).
      * **scan** — the exact predicate-fused brute scan: the
        ``kernels/scan_topk`` Pallas kernel when ``backend=
        "pallas_gather_l2_filter"``, the jnp oracle ``scan_topk_ref``
        when ``backend="jnp"`` (bit-identical outputs — pinned).
        Structurally padded index rows are NaN-masked out of the scan
        once at build time (they are unreachable by construction in the
        graph path, but a scan visits every row). Scan lanes report
        ``hops=0`` and are exact: recall 1.0 by construction.

    Dispatch (``"auto"``): scan iff ``0 < card <= threshold``. Zero-card
    queries (provably empty range, e.g. the serving layer's pad lanes)
    go to the graph program, which exits its hop loop immediately —
    both programs return all (-1, +inf) for them, but the graph exit is
    near-free while a scan lane always pays a full corpus pass. Mixed
    batches split into two sub-batches padded up to the next power of
    two (bounded trace count, ≤ 2× padding work) with empty-range pad
    lanes, and results scatter back by lane.

    The legacy ``dist_fn`` override affects the graph path's scoring
    only (the scan's contract is exactness against the jnp oracle).
    """

    def __init__(self, index, params: SearchParams, *, dist_fn=None,
                 interpret: Optional[bool] = None,
                 on_undersized: str = "adjust",
                 plan_cache: Optional["collections.OrderedDict"] = None,
                 plan_salt: bytes = b""):
        if isinstance(index, KHIIndex):
            index = device_put_index(index)
        # duck-typed ShardedKHI check (sharded.py imports this module)
        self._sharded = hasattr(index, "offsets") and hasattr(index, "di")
        di = index.di if self._sharded else index
        self.params = p = validate_search_params(params, di,
                                                 on_undersized=on_undersized)
        # quantized score path (§12): make sure the index carries the
        # replica the scorers will stream (derive it here if the caller
        # handed a bare f32 index)
        if p.quant != "none" and di.qvecs is None:
            di = with_quant_replica(di, p.quant)
            index = (dataclasses.replace(index, di=di) if self._sharded
                     else di)
        self.index = index
        self._dist_fn = dist_fn
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        self._interpret = interpret

        # per-shard real row counts: the tree root's count — DeviceIndex
        # arrays may be padded (pad_n / shard stacking) past the corpus
        root = np.atleast_1d(np.asarray(jax.device_get(di.root)))
        count = np.asarray(jax.device_get(di.count))
        if count.ndim == 1:
            count = count[None]
        self._n_shard = count[np.arange(root.shape[0]), root]
        self.n_total = int(self._n_shard.sum())
        self.scan_threshold = int(p.scan_threshold) or max(
            1, int(DEFAULT_SCAN_FRAC * self.n_total))

        # NaN-mask structurally padded rows ONCE: NaN fails every range
        # predicate (even unconstrained ±inf bounds), so padded rows can
        # never enter a scan's top-k — kernels/scan_topk.py's convention
        N = di.attrs.shape[-2]
        valid = np.arange(N)[None, :] < self._n_shard[:, None]
        if not self._sharded:
            valid = valid[0]
        self._scan_attrs = jnp.where(jnp.asarray(valid)[..., None],
                                     di.attrs, jnp.nan)

        # name -> (jitted program, its leading index arguments), for
        # compiled_text
        self._programs: dict = {}
        self._graph_fn = (self._build_graph_fn()
                          if p.strategy in ("graph", "auto", "hybrid")
                          else None)
        self._scan_fn = (self._build_scan_fn()
                         if p.strategy in ("scan", "auto") else None)
        self._estimators = (self._build_estimators()
                            if p.strategy in ("auto", "hybrid") else None)
        # hybrid per-node dispatch state (§12): the node threshold, the
        # host (S, P) start/count planes the window extents come from,
        # and the position-ordered (DFS) scan replica the windowed
        # kernel streams contiguously
        self.node_scan_threshold = (int(p.node_scan_threshold)
                                    or self.scan_threshold)
        if p.strategy == "hybrid":
            start = np.asarray(jax.device_get(di.start))
            count = np.asarray(jax.device_get(di.count))
            self._node_start = np.atleast_2d(start)
            self._node_count = np.atleast_2d(count)
            self._build_pos_replica()
        # Plan cache (§10) — optionally SHARED across planners. The cached
        # value (the routing cardinality bound) depends only on the range
        # box and the estimator state (index epoch + tombstones), NOT on
        # any SearchParams knob: the dispatch threshold is applied at
        # decision time. The serving layer's degradation ladder (§13)
        # exploits this — one cache serves every tier, so a box estimated
        # at full quality re-dispatches for free when the ladder steps the
        # same box down. ``plan_salt`` tags every key with the caller's
        # estimator-state identity (tier-INdependent, epoch-dependent) so
        # a shared cache can never serve a stale epoch's bound.
        self._plan_cache: "collections.OrderedDict[bytes, int]" = (
            collections.OrderedDict() if plan_cache is None else plan_cache)
        self._plan_salt = plan_salt
        self.plan_cache_size = 65536
        # predicate-compiler state (§15), built lazily on the first
        # search_expr: the jitted bitmask-scan program and the host copy
        # of the NaN-masked scan attrs the mask evaluator reads
        self._mask_fn = None
        self._host_scan_attrs: Optional[np.ndarray] = None

    def _build_pos_replica(self) -> None:
        """Position-ordered copies of the scan corpus: row i of
        ``_pos_vecs`` is the object at DFS rank i (``order[i]``), so an
        antichain node's objects are the contiguous slice
        ``[start, start + count)`` — what scan_topk_windows DMAs. The
        attrs copy starts from ``_scan_attrs`` so structural padding and
        streaming tombstones stay NaN; recomputed on refresh_index."""
        di = self.index.di if self._sharded else self.index
        order = di.order[..., None]
        self._pos_vecs = jnp.take_along_axis(di.vecs, order, axis=-2)
        self._pos_attrs = jnp.take_along_axis(self._scan_attrs, order,
                                              axis=-2)

    # --------------------------------------------------------- plan pass
    def _build_estimators(self, deleted_rows=None):
        """One HostCardEstimator per shard from host copies of the
        flattened tree (small next to the vector plane; fetched once per
        Planner/epoch). ``deleted_rows`` — per-shard LOCAL row-id arrays
        of streaming tombstones (DESIGN.md §11) — subtracts the dead rows
        from each node's count so the routing bound covers only *live*
        objects and deletes never inflate dispatch estimates."""
        from .router import deleted_per_node

        di = self.index.di if self._sharded else self.index
        host = {f: np.asarray(jax.device_get(getattr(di, f)))
                for f in ("left", "right", "dim", "bl", "lo", "hi",
                          "count", "start", "order", "root")}
        if not self._sharded:
            host = {k: v[None] for k, v in host.items()}
        ests = []
        for s in range(host["left"].shape[0]):
            count = host["count"][s].astype(np.int64)
            if deleted_rows is not None and np.asarray(
                    deleted_rows[s]).size:
                n_s = int(self._n_shard[s])
                count = count - deleted_per_node(
                    host["order"][s][:n_s], host["start"][s], count,
                    deleted_rows[s])
            ests.append(HostCardEstimator(
                host["left"][s], host["right"][s], host["dim"][s],
                host["bl"][s], host["lo"][s], host["hi"][s], count,
                int(host["root"][s])))
        return ests

    def _cards(self, qlo: np.ndarray, qhi: np.ndarray) -> np.ndarray:
        """Per-query routing bound through the plan cache (repeated boxes
        re-dispatch without re-estimating)."""
        B = qlo.shape[0]
        out = np.zeros(B, np.int64)
        keys, miss = [], []
        for i in range(B):
            h = hashlib.blake2b(digest_size=16)
            h.update(self._plan_salt)
            h.update(qlo[i].tobytes())
            h.update(qhi[i].tobytes())
            key = h.digest()
            keys.append(key)
            hit = self._plan_cache.get(key)
            if hit is None:
                miss.append(i)
            else:
                self._plan_cache.move_to_end(key)
                out[i] = hit
        if miss:
            mi = np.asarray(miss)
            card = sum(est.cards(qlo[mi], qhi[mi])
                       for est in self._estimators)
            for j, i in enumerate(miss):
                out[i] = card[j]
                self._plan_cache[keys[i]] = int(card[j])
            while len(self._plan_cache) > self.plan_cache_size:
                self._plan_cache.popitem(last=False)
        return out

    # -------------------------------------------------- streaming refresh
    def refresh_index(self, index, *, deleted_rows=None) -> None:
        """Rebind to a functionally-updated index of IDENTICAL shapes —
        the streaming tombstone path (DESIGN.md §11), where a delete NaNs
        attr rows without touching any other array. The jitted programs
        read ``self.index`` / ``self._scan_attrs`` at call time, so this
        swaps what they see without a retrace; only the host-side plan
        state (scan mask, estimators with tombstone-adjusted counts, plan
        cache) is recomputed. Anything shape-changing must build a fresh
        Planner instead."""
        if isinstance(index, KHIIndex):
            raise TypeError("refresh_index takes an already-device-resident "
                            "index (same shapes as the installed one)")
        sharded = hasattr(index, "offsets") and hasattr(index, "di")
        di_new = index.di if sharded else index
        di_old = self.index.di if self._sharded else self.index
        if sharded != self._sharded or di_new.attrs.shape != \
                di_old.attrs.shape or di_new.vecs.shape != di_old.vecs.shape:
            raise ValueError("refresh_index requires identical index shapes"
                             " (use a new Planner for a new epoch)")
        # quant-replica coherence (§12): tombstone refreshes preserve
        # qvecs/qscale (deletes touch attrs only), but re-derive if the
        # caller handed back a bare f32 index
        if self.params.quant != "none" and di_new.qvecs is None:
            di_new = with_quant_replica(di_new, self.params.quant)
            index = (dataclasses.replace(index, di=di_new) if sharded
                     else di_new)
        self.index = index
        N = di_new.attrs.shape[-2]
        valid = np.arange(N)[None, :] < self._n_shard[:, None]
        if not self._sharded:
            valid = valid[0]
        self._scan_attrs = jnp.where(jnp.asarray(valid)[..., None],
                                     di_new.attrs, jnp.nan)
        self._host_scan_attrs = None   # bitmask evaluator re-fetches (§15)
        if self.params.strategy in ("auto", "hybrid"):
            self._estimators = self._build_estimators(deleted_rows)
        if self.params.strategy == "hybrid":
            self._build_pos_replica()
        self._plan_cache.clear()

    # ------------------------------------------------------ device programs
    def _build_graph_fn(self):
        p = self.params
        scorer, exact = resolve_scorer_pair(p, dist_fn=self._dist_fn,
                                            interpret=self._interpret)
        if not self._sharded:
            @jax.jit
            def graph(di, q, qlo, qhi):
                fn = functools.partial(_query_one, p=p, scorer=scorer,
                                       exact_scorer=exact)
                return jax.vmap(lambda qq, lo, hi: fn(di, qq, lo, hi))(
                    q, qlo, qhi)
            self._programs["graph"] = (graph, lambda: (self.index,))
            return lambda q, qlo, qhi: graph(self.index, q, qlo, qhi)

        from .sharded import _merge_topk, _shard_search
        S = self.index.num_shards

        @jax.jit
        def graph_sharded(skhi, q, qlo, qhi):
            def per_shard(di, off):
                return _shard_search(di, off, S, q, qlo, qhi, p, scorer,
                                     exact_scorer=exact)
            gids, dists, hops = jax.vmap(per_shard)(skhi.di, skhi.offsets)
            mi, md = _merge_topk(gids, dists, p.k)
            return mi, md, jnp.max(hops, axis=0)

        self._programs["graph"] = (graph_sharded, lambda: (self.index,))
        return lambda q, qlo, qhi: graph_sharded(self.index, q, qlo, qhi)

    def _build_scan_fn(self):
        p = self.params
        interpret = self._interpret
        use_kernel = p.backend == "pallas_gather_l2_filter"

        def scan_one(di, shard, attrs_nan, q, qlo, qhi):
            return _scan_shard_topk(di, shard, attrs_nan, q, qlo, qhi, p,
                                    use_kernel=use_kernel,
                                    interpret=interpret)

        if not self._sharded:
            @jax.jit
            def scan(di, attrs_nan, q, qlo, qhi):
                return scan_one(di, None, attrs_nan, q, qlo, qhi)
            self._programs["scan"] = (
                scan, lambda: (self.index, self._scan_attrs))
            return lambda q, qlo, qhi: scan(self.index, self._scan_attrs,
                                            q, qlo, qhi)

        from .sharded import _local_to_global, _merge_topk
        S = self.index.num_shards

        @jax.jit
        def scan_sharded(skhi, attrs_nan, q, qlo, qhi):
            gi, gd = [], []
            for s in range(S):       # static unroll: S identical-shape scans
                ids, dd = scan_one(skhi.di, s, attrs_nan[s], q, qlo, qhi)
                gids = _local_to_global(ids, skhi.offsets[s], S)
                gi.append(gids)
                gd.append(jnp.where(gids >= 0, dd, jnp.inf))
            return _merge_topk(jnp.stack(gi), jnp.stack(gd), p.k)

        self._programs["scan"] = (
            scan_sharded, lambda: (self.index, self._scan_attrs))
        return lambda q, qlo, qhi: scan_sharded(self.index, self._scan_attrs,
                                                q, qlo, qhi)

    def compiled_text(self, batch: int) -> dict:
        """Compiled HLO text of each whole-batch device program ("graph",
        "scan") at ``batch`` lanes — e.g. to check that the Pallas kernels
        lowered to Mosaic custom calls and not to the interpreter."""
        di = self.index.di if self._sharded else self.index
        q = jax.ShapeDtypeStruct((batch, di.vecs.shape[-1]), jnp.float32)
        box = jax.ShapeDtypeStruct((batch, di.attrs.shape[-1]), jnp.float32)
        return {name: fn.lower(*args(), q, box, box).compile().as_text()
                for name, (fn, args) in self._programs.items()}

    # ------------------------------------------------- hybrid window pass
    def _build_windows(self, small_nodes: list, idx: np.ndarray, bp: int):
        """Window arrays for the lanes ``idx``, padded to ``bp`` rows:
        (starts (S, bp, W) int32, counts (S, bp, W) int32, w_cap). Each
        lane's windows are its small antichain nodes' raw
        ``[start, count]`` DFS extents, sorted ascending by start (the
        windowed kernel's tie-break contract); W and w_cap round up to
        powers of two to bound the trace count. Pad windows are
        (-1, 0)."""
        S = len(small_nodes)
        lanes_per_shard = []
        max_w, max_c = 1, 1
        for s in range(S):
            sub = small_nodes[s][idx]                 # (B', P)
            lanes = []
            for b in range(sub.shape[0]):
                nodes = np.nonzero(sub[b])[0]
                st = self._node_start[s][nodes]
                ct = self._node_count[s][nodes]
                keep = ct > 0
                st, ct = st[keep], ct[keep]
                o = np.argsort(st, kind="stable")
                st, ct = st[o], ct[o]
                lanes.append((st, ct))
                if st.size:
                    max_w = max(max_w, st.size)
                    max_c = max(max_c, int(ct.max()))
            lanes_per_shard.append(lanes)
        W = pow2_at_least(max_w)
        w_cap = pow2_at_least(max_c)
        starts = np.full((S, bp, W), -1, np.int32)
        counts = np.zeros((S, bp, W), np.int32)
        for s in range(S):
            for b, (st, ct) in enumerate(lanes_per_shard[s]):
                starts[s, b, : st.size] = st
                counts[s, b, : ct.size] = ct
        return starts, counts, w_cap

    def _run_windows(self, qs, lo, hi, starts, counts, w_cap: int):
        """Exact windowed scan over the position-ordered replica
        (DESIGN.md §12): positions come back from the kernel/oracle,
        map through ``order`` to ids (then to global ids per shard),
        and sharded lanes merge like every other top-k stream. Window
        lanes report hops = 0 (no graph walk)."""
        p = self.params
        use_kernel = p.backend == "pallas_gather_l2_filter"
        q, qlo_, qhi_ = (jnp.asarray(qs), jnp.asarray(lo), jnp.asarray(hi))
        if not self._sharded:
            ids, dd = _windows_one(
                self._pos_vecs, self._pos_attrs, self.index.order,
                q, qlo_, qhi_, jnp.asarray(starts[0]),
                jnp.asarray(counts[0]), k=p.k, w_cap=w_cap,
                use_kernel=use_kernel, interpret=self._interpret)
        else:
            ids, dd = _windows_sharded(
                self._pos_vecs, self._pos_attrs, self.index.di.order,
                self.index.offsets, q, qlo_, qhi_, jnp.asarray(starts),
                jnp.asarray(counts), k=p.k, w_cap=w_cap,
                use_kernel=use_kernel, interpret=self._interpret)
        return (np.asarray(ids), np.asarray(dd),
                np.zeros(qs.shape[0], np.int32))

    # -------------------------------------------------------- host dispatch
    def plan(self, qlo: np.ndarray, qhi: np.ndarray) -> Plan:
        """Per-query dispatch decisions for one batch of range boxes."""
        qlo = np.ascontiguousarray(qlo, np.float32)
        qhi = np.ascontiguousarray(qhi, np.float32)
        B = qlo.shape[0]
        p = self.params
        if p.strategy == "graph":
            return Plan(card=np.full(B, -1, np.int64),
                        use_scan=np.zeros(B, bool),
                        threshold=self.scan_threshold)
        if p.strategy == "scan":
            return Plan(card=np.full(B, -1, np.int64),
                        use_scan=np.ones(B, bool),
                        threshold=self.scan_threshold)
        card = self._cards(qlo, qhi)
        if p.strategy != "hybrid":
            use_scan = (card > 0) & (card <= self.scan_threshold)
            return Plan(card=card, use_scan=use_scan,
                        threshold=self.scan_threshold)
        # hybrid (§12): classify each lane by its antichain's node sizes.
        # Smallness uses RAW node counts (the cost of scanning the DFS
        # extent — tombstoned rows still stream through the kernel);
        # ``card`` stays tombstone-adjusted for the exactness gate.
        thr = self.node_scan_threshold
        small_nodes = []
        n_small = np.zeros(B, np.int64)
        n_large = np.zeros(B, np.int64)
        for s, est in enumerate(self._estimators):
            anti = est.antichain(qlo, qhi)            # (B, P) bool
            cnt = self._node_count[s]
            small = anti & ((cnt > 0) & (cnt <= thr))[None, :]
            small_nodes.append(small)
            n_small += small.sum(axis=1)
            n_large += (anti & (cnt > thr)[None, :]).sum(axis=1)
        mode = np.zeros(B, np.int8)
        mode[(n_large == 0) & (card > 0)] = 1          # pure-window: exact
        mode[(n_large > 0) & (n_small > 0)] = 2        # mixed
        return Plan(card=card, use_scan=(mode == 1),
                    threshold=self.scan_threshold, node_threshold=thr,
                    mode=mode, n_windows=n_small, small_nodes=small_nodes)

    @staticmethod
    def _pad_pow2(qs, lo, hi):
        """Pad a sub-batch to the next power of two with empty-range lanes
        (lo=+inf > hi=-inf: zero entries and zero in-range rows), bounding
        the jit trace count at O(log B) shapes per strategy."""
        b = qs.shape[0]
        bp = pow2_at_least(b)
        pad = bp - b
        if pad:
            qs = np.concatenate([qs, np.zeros((pad,) + qs.shape[1:],
                                              np.float32)])
            lo = np.concatenate([lo, np.full((pad,) + lo.shape[1:],
                                             np.inf, np.float32)])
            hi = np.concatenate([hi, np.full((pad,) + hi.shape[1:],
                                             -np.inf, np.float32)])
        return qs, lo, hi

    def _run_graph(self, qs, lo, hi):
        ids, dists, hops = self._graph_fn(jnp.asarray(qs), jnp.asarray(lo),
                                          jnp.asarray(hi))
        return np.asarray(ids), np.asarray(dists), np.asarray(hops)

    def _run_scan(self, qs, lo, hi):
        ids, dists = self._scan_fn(jnp.asarray(qs), jnp.asarray(lo),
                                   jnp.asarray(hi))
        return (np.asarray(ids), np.asarray(dists),
                np.zeros(qs.shape[0], np.int32))

    def search(self, queries, qlo, qhi):
        """(B, d) × (B, m) × (B, m) -> (ids (B, k) int32, dists (B, k)
        f32, hops (B,) int32, Plan). Global ids for a sharded index;
        scan lanes carry hops = 0."""
        queries = np.ascontiguousarray(queries, np.float32)
        qlo = np.ascontiguousarray(qlo, np.float32)
        qhi = np.ascontiguousarray(qhi, np.float32)
        plan = self.plan(qlo, qhi)
        B, k = queries.shape[0], self.params.k
        if plan.mode is not None:
            return self._search_hybrid(queries, qlo, qhi, plan)
        scan_idx = np.nonzero(plan.use_scan)[0]
        graph_idx = np.nonzero(~plan.use_scan)[0]
        if not len(graph_idx):
            ids, dists, hops = self._run_scan(queries, qlo, qhi)
            return ids, dists, hops, plan
        if not len(scan_idx):
            ids, dists, hops = self._run_graph(queries, qlo, qhi)
            return ids, dists, hops, plan
        out_ids = np.full((B, k), -1, np.int32)
        out_d = np.full((B, k), np.inf, np.float32)
        out_h = np.zeros((B,), np.int32)
        for idx, run in ((graph_idx, self._run_graph),
                         (scan_idx, self._run_scan)):
            qs, lo, hi = self._pad_pow2(queries[idx], qlo[idx], qhi[idx])
            ids, dists, hops = run(qs, lo, hi)
            out_ids[idx] = ids[: len(idx)]
            out_d[idx] = dists[: len(idx)]
            out_h[idx] = hops[: len(idx)]
        return out_ids, out_d, out_h, plan

    # --------------------------------------------- compiled predicates (§15)
    def _build_mask_fn(self):
        p = self.params
        interpret = self._interpret
        use_kernel = p.backend == "pallas_gather_l2_filter"

        if not self._sharded:
            @jax.jit
            def mask_scan(di, mask, q):
                return _mask_scan_one(di.vecs, mask, q, p.k,
                                      use_kernel=use_kernel,
                                      interpret=interpret)
            return lambda mask, q: mask_scan(self.index, mask, q)

        from .sharded import _local_to_global, _merge_topk
        S = self.index.num_shards

        @jax.jit
        def mask_sharded(skhi, mask, q):
            gi, gd = [], []
            for s in range(S):   # static unroll: S identical-shape scans
                ids, dd = _mask_scan_one(skhi.di.vecs[s], mask[s], q, p.k,
                                         use_kernel=use_kernel,
                                         interpret=interpret)
                gids = _local_to_global(ids, skhi.offsets[s], S)
                gi.append(gids)
                gd.append(jnp.where(gids >= 0, dd, jnp.inf))
            return _merge_topk(jnp.stack(gi), jnp.stack(gd), p.k)

        return lambda mask, q: mask_sharded(self.index, mask, q)

    def _run_mask(self, queries: np.ndarray, prog):
        """Dense-fallback execution (§15): evaluate the normalized
        expression host-side over the NaN-masked scan attrs (structural
        padding and streaming tombstones fail every expression) into a
        per-row plane, then one exact f32 bitmask-fused pass — same
        query-count pow2 padding discipline as the strategy sub-batches."""
        from .predicate import eval_expr

        if self._mask_fn is None:
            self._mask_fn = self._build_mask_fn()
        if self._host_scan_attrs is None:
            self._host_scan_attrs = np.asarray(
                jax.device_get(self._scan_attrs))
        mask = eval_expr(prog.expr, self._host_scan_attrs).astype(np.float32)
        B = queries.shape[0]
        bp = pow2_at_least(B)
        qs = queries if bp == B else np.concatenate(
            [queries, np.zeros((bp - B,) + queries.shape[1:], np.float32)])
        ids, dd = self._mask_fn(jnp.asarray(mask), jnp.asarray(qs))
        return (np.asarray(ids)[:B], np.asarray(dd)[:B],
                np.zeros(B, np.int32))

    @staticmethod
    def _count_lanes(plan: Plan, lanes: dict, B: int) -> None:
        """Fold one box's dispatch into the per-strategy lane counters
        (PredicatePlan.lanes; mixed hybrid lanes count under both)."""
        if plan.mode is not None:
            lanes["graph"] += int(((plan.mode == 0) | (plan.mode == 2)).sum())
            lanes["window"] += int(((plan.mode == 1) | (plan.mode == 2)).sum())
        else:
            ns = int(plan.use_scan.sum())
            lanes["scan"] += ns
            lanes["graph"] += B - ns

    def search_expr(self, queries, expr):
        """Compiled-predicate search (DESIGN.md §15): (B, d) queries × one
        boolean filter expression -> (ids (B, k) int32, dists (B, k) f32,
        hops (B,) int32, PredicatePlan).

        ``"boxes"`` programs run each disjoint box through the full
        ``search`` dispatch (graph/scan/auto/hybrid per disjunct, plan
        cache shared) and merge the per-box streams with ``_merge_dedup``
        — sound with plain best-dist-per-id semantics because the cover
        is disjoint: no row can appear under two boxes, dedup only ever
        collapses the (+inf, -1) pads. ``hops`` sums over boxes (the
        total graph work the expression cost). ``"bitmask"`` programs
        run one exact f32 fallback pass (hops 0)."""
        from .predicate import compile_expr

        queries = np.ascontiguousarray(queries, np.float32)
        p = self.params
        di = self.index.di if self._sharded else self.index
        m = int(di.attrs.shape[-1])
        prog = compile_expr(expr, m, box_budget=p.box_budget)
        B, k = queries.shape[0], p.k
        lanes = {"graph": 0, "scan": 0, "window": 0}
        if prog.mode == "bitmask":
            ids, dists, hops = self._run_mask(queries, prog)
            lanes["scan"] = B
            return ids, dists, hops, PredicatePlan(
                mode="bitmask", n_boxes=0, lanes=lanes, box_plans=[],
                program=prog)
        out_ids = out_d = None
        out_h = np.zeros(B, np.int32)
        box_plans = []
        for b in range(prog.n_boxes):
            qlo = np.ascontiguousarray(
                np.broadcast_to(prog.lo[b], (B, m)), np.float32)
            qhi = np.ascontiguousarray(
                np.broadcast_to(prog.hi[b], (B, m)), np.float32)
            ids, dists, hops, plan = self.search(queries, qlo, qhi)
            box_plans.append(plan)
            self._count_lanes(plan, lanes, B)
            out_h += hops
            if out_ids is None:
                out_ids, out_d = ids, dists
            else:
                out_ids, out_d = _merge_dedup(out_ids, out_d, ids, dists, k)
        return out_ids, out_d, out_h, PredicatePlan(
            mode="boxes", n_boxes=prog.n_boxes, lanes=lanes,
            box_plans=box_plans, program=prog)

    def _search_hybrid(self, queries, qlo, qhi, plan: Plan):
        """Three-way lane split (§12): mode 0 = graph walk, mode 1 =
        pure-window (every antichain node small — exact by construction,
        hops = 0), mode 2 = mixed — the UNRESTRICTED graph walk plus the
        small-node windows, merged host-side with id-level dedup (the
        graph stream may re-find window rows)."""
        B, k = queries.shape[0], self.params.k
        out_ids = np.full((B, k), -1, np.int32)
        out_d = np.full((B, k), np.inf, np.float32)
        out_h = np.zeros((B,), np.int32)
        for m in (0, 1, 2):
            idx = np.nonzero(plan.mode == m)[0]
            if not len(idx):
                continue
            qs, lo, hi = self._pad_pow2(queries[idx], qlo[idx], qhi[idx])
            if m == 0:
                ids, dists, hops = self._run_graph(qs, lo, hi)
            else:
                starts, counts, w_cap = self._build_windows(
                    plan.small_nodes, idx, qs.shape[0])
                ids, dists, hops = self._run_windows(qs, lo, hi, starts,
                                                     counts, w_cap)
                if m == 2:
                    gids, gd, hops = self._run_graph(qs, lo, hi)
                    ids, dists = _merge_dedup(
                        gids[: len(idx)], gd[: len(idx)],
                        ids[: len(idx)], dists[: len(idx)], k)
            out_ids[idx] = ids[: len(idx)]
            out_d[idx] = dists[: len(idx)]
            out_h[idx] = hops[: len(idx)]
        return out_ids, out_d, out_h, plan
