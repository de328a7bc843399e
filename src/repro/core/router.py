"""Tree routing (Algorithm 1) — Phase A of the two-phase query pipeline
(DESIGN.md §9), plus the planner's routing-state cardinality estimators
(DESIGN.md §10).

Routing finds up to ``c_e`` entry points in O_B by walking the attribute
partition tree. Two device implementations share one contract
(``route(di, qlo, qhi, p) -> ((c_e,) int32 entry ids, -1 padded, in DFS
order; () int32 in-range cardinality bound; (H,) int32 chunks each tree
level swept)``) and return **identical entry vectors** (pinned by
tests/test_router.py):

  * ``route_dfs`` — the legacy per-query stack DFS ``lax.while_loop``
    (one node pop per iteration; it sweeps no chunks). Inside the
    vmapped batch every lane pays the slowest lane's pop count: the
    while_loop is lockstep, so a single deep query serializes the whole
    batch.
  * ``route_level_sync`` — the production router: a ``lax.scan`` over
    tree **levels** (height is O(log n), Lemma 1) with a per-query
    frontier of (node, D-bitmask) pairs, ``frontier_cap`` slots wide.
    The frontier is compacted: its live nodes sit in ``[0, live)``, in
    left-to-right tree order. Each level sweeps only that live prefix,
    in chunks of C = min(``_CHUNK``, ``frontier_cap``) slots: a chunk
    classifies its nodes, compacts their children into the next
    frontier and entry-scans its scanned nodes in DFS-rank order,
    ``c_e`` at a time and ``_SCAN_STEP`` objects per node per step,
    until none of them can enter the answer. Under ``vmap`` a level's
    chunk loop runs to the batch's largest ``ceil(live / C)``, so a
    batch pays, per level, for its widest lane's live frontier and not
    for the tree's widest level. (A whole-frontier ``(F, scan_budget)``
    gather grows with n twice over: at one 1M-row shard F is ~4.3e5
    nodes and scan_budget ~5e4 rows.)

Why the two return the same entries: the DFS collects entries in pop
order (right child pushed last, popped first — right-first pre-order)
and stops after ``c_e``. The set of *scannable* nodes (covered or leaf)
is traversal-order independent, and scanned nodes form an antichain
(a scanned node is never descended), so their object ranges
``[start, start+count)`` are disjoint — which makes right-first
pre-order over them exactly **descending range end**. The level-sync
router therefore tags each candidate entry with the key
``n - (start + count)``, keeps the ``c_e`` smallest keys across the
sweep (a sorted running merge per chunk), and returns them ascending:
the same entries, in the same order, as the DFS with its early stop
(the stop only ever drops larger keys). The c_e smallest keys of a set
do not depend on the order it is visited in, so the chunking leaves
the entries as they are; nor does it move a child's frontier slot,
which is a running offset over the level's earlier chunks plus the
chunk's own exclusive cumsum: the level-wide cumsum. The numpy twin is
``query_ref.range_filter_level``.

The frontier width is bounded by ``SearchParams.frontier_cap`` with the
same overflow-clamp semantics as the DFS ``stack_cap`` (excess pushes
drop); ``required_frontier_cap(di)`` derives the exact sufficient value
(max nodes on any tree level) and ``engine.validate_search_params``
raises/adjusts undersized configs, like it does for scan_budget.

**Cardinality bound** (DESIGN.md §10): every in-range object lives in
exactly one *scanned* node (disjoint branches are dropped only when
provably empty on the split dim, and the scanned antichain covers every
surviving branch), so the sum of ``count`` over scanned nodes is an
upper bound on |O_B| — exact on nodes whose rectangle is genuinely
contained (covered with no blacklisted dims), an overcount only on
leaves and BL-covered nodes, whose object counts are small by
construction. Both routers accumulate it as a byproduct of the
traversal they already do; it is the planner's selectivity estimate.
Caveat: the DFS early-stops after ``c_e`` entries, so *its* sum covers
only the visited prefix of the antichain and is NOT a bound — the
planner therefore requires ``router="level"`` (the sweep always runs
all levels). ``route_level_card`` is the estimate-only form and
``route_level_windows`` the hybrid form: the same chunked traversal
(``_sweep``), with no entry scans (the expensive part of routing).
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Callable

import jax
import jax.numpy as jnp
import numpy as np

if TYPE_CHECKING:  # pragma: no cover
    from .engine import DeviceIndex, SearchParams

__all__ = ["ROUTERS", "resolve_router", "route_dfs", "route_level_sync",
           "route_level_card", "route_level_windows", "HostCardEstimator",
           "deleted_per_node", "required_frontier_cap"]

ROUTERS = ("level", "dfs")

_I32_MAX = np.iinfo(np.int32).max
# objects read per node per entry-scan step of the level router: a node's
# first in-box object is usually among its first few, and scan_budget (the
# largest scannable node, ~5e4 rows at a 1M-row shard) only bounds how far
# the scan may go
_SCAN_STEP = 128
# frontier slots per chunk of the level sweep: a level costs
# ceil(live / _CHUNK) chunk iterations, so a small chunk pays the loop's
# per-iteration cost more often and a large one sweeps more dead slots.
# On a v5e, 1024-4096 run the router fastest; 16384 keeps the graph
# benchmark cells under the rate their query pool can measure (PERF.md
# §6, §7)
_CHUNK = 16384


def _root_D0(di, qlo, qhi, m: int) -> jax.Array:
    """D seeded with dims the root rectangle already covers."""
    root_cov = ((di.lo[di.root] >= qlo) & (di.hi[di.root] <= qhi))
    return jnp.sum(jnp.where(root_cov, 1 << jnp.arange(m), 0)).astype(jnp.int32)


# --------------------------------------------------------------------------
# Legacy per-query stack DFS (reference form of the device router)
# --------------------------------------------------------------------------

def route_dfs(di, qlo: jax.Array, qhi: jax.Array, p):
    """Returns (entry-point object ids (c_e,), -1 padded, DFS order;
    () int32 sum of scanned-node counts; (H,) int32 zeros: no chunks).
    The DFS early-stops after c_e entries, so its count sum covers only
    the visited antichain prefix — NOT an |O_B| bound (module
    docstring); the planner requires the level router for that."""
    m = di.attrs.shape[1]
    full = (1 << m) - 1
    S = p.stack_cap
    # padded order hoisted out of the loop body — the pop body used to
    # re-pad (n,) -> (n + scan_budget,) on every node pop
    order_pad = jnp.pad(di.order, (0, p.scan_budget))

    D0 = _root_D0(di, qlo, qhi, m)

    def scan_entry(node):
        s = di.start[node]
        win = jax.lax.dynamic_slice(order_pad, (s,), (p.scan_budget,))
        in_node = jnp.arange(p.scan_budget) < di.count[node]
        a = di.attrs[win]
        ok = in_node & jnp.all((a >= qlo) & (a <= qhi), axis=-1)
        idx = jnp.argmax(ok)
        return jnp.where(ok.any(), win[idx], -1).astype(jnp.int32)

    State = tuple  # (stack_node, stack_D, sp, entries, n_e, card, steps)
    stack_node = jnp.full((S,), -1, jnp.int32).at[0].set(di.root)
    stack_D = jnp.zeros((S,), jnp.int32).at[0].set(D0)
    entries = jnp.full((p.c_e,), -1, jnp.int32)
    state: State = (stack_node, stack_D, jnp.int32(1), entries,
                    jnp.int32(0), jnp.int32(0), jnp.int32(0))

    def cond(st):
        _, _, sp, _, n_e, _, steps = st
        return (sp > 0) & (n_e < p.c_e) & (steps < p.max_steps)

    def body(st):
        stack_node, stack_D, sp, entries, n_e, card, steps = st
        node = stack_node[sp - 1]
        D = stack_D[sp - 1] | di.bl[node]
        sp = sp - 1

        is_full = D == full
        is_leaf = di.left[node] < 0

        # entry scan for covered nodes AND leaves (leaf fallback — see
        # query_ref.range_filter for the rationale)
        do_scan = is_full | is_leaf
        card = card + jnp.where(do_scan, di.count[node], 0)
        e = jnp.where(do_scan, scan_entry(node), -1)
        got = do_scan & (e >= 0)
        entries = entries.at[jnp.where(got, n_e, p.c_e)].set(e, mode="drop")
        n_e = n_e + got.astype(jnp.int32)

        # children pushes (only when internal & not full)
        dsp = di.dim[node]
        cl, cr = di.left[node], di.right[node]
        covered = ((D >> dsp) & 1) == 1

        def child_push(pc):
            lc = di.lo[pc, dsp]
            rc = di.hi[pc, dsp]
            disjoint = (lc > qhi[dsp]) | (rc < qlo[dsp])
            contained = (lc >= qlo[dsp]) & (rc <= qhi[dsp])
            newD = jnp.where(contained, D | (1 << dsp), D)
            valid = ~disjoint
            # covered split dim: always push with unchanged D
            newD = jnp.where(covered, D, newD)
            valid = jnp.where(covered, True, valid)
            return valid & ~is_full & ~is_leaf, newD

        vl, Dl = child_push(cl)
        vr, Dr = child_push(cr)
        # push left first (popped last) to match the reference DFS order
        slot_l = jnp.where(vl, sp, S)
        stack_node = stack_node.at[slot_l].set(cl, mode="drop")
        stack_D = stack_D.at[slot_l].set(Dl, mode="drop")
        sp = sp + vl.astype(jnp.int32)
        slot_r = jnp.where(vr, sp, S)
        stack_node = stack_node.at[slot_r].set(cr, mode="drop")
        stack_D = stack_D.at[slot_r].set(Dr, mode="drop")
        sp = sp + vr.astype(jnp.int32)
        sp = jnp.minimum(sp, S)  # overflow clamp (documented bound)
        return (stack_node, stack_D, sp, entries, n_e, card, steps + 1)

    state = jax.lax.while_loop(cond, body, state)
    return state[3], state[5], jnp.zeros((di.nbrs.shape[1],), jnp.int32)


# --------------------------------------------------------------------------
# Level-synchronous batched router (production form)
# --------------------------------------------------------------------------

def _require_frontier(F: int) -> None:
    if F <= 0:
        raise ValueError(
            "SearchParams.frontier_cap is unset (0 = derive from the "
            "index): resolve it with derive_search_params / "
            "validate_search_params, or build the search via "
            "make_search_fn(p, di=...) / search_batch, which do. An "
            "arbitrary fixed width would silently drop router branches.")


def _frontier_step(di, qlo, qhi, full: int, fnode, fD, alive):
    """One chunk of a level: classify its frontier slots (scanned
    antichain nodes vs nodes to expand) and list their children in slot
    order, left child first. Returns (node (C,) leaf-safe ids,
    do_scan (C,) bool, and the (2C,) candidate children: node, D,
    valid)."""
    node = jnp.where(alive, fnode, 0)
    D = jnp.where(alive, fD | di.bl[node], 0)
    is_full = D == full
    is_leaf = di.left[node] < 0
    do_scan = alive & (is_full | is_leaf)

    expand = alive & ~is_full & ~is_leaf
    dsp = jnp.maximum(di.dim[node], 0)              # leaf-safe (masked)
    covered = ((D >> dsp) & 1) == 1
    qlod, qhid = qlo[dsp], qhi[dsp]

    def child(pc):
        csafe = jnp.maximum(pc, 0)
        lc = di.lo[csafe, dsp]
        rc = di.hi[csafe, dsp]
        disjoint = (lc > qhid) | (rc < qlod)
        contained = (lc >= qlod) & (rc <= qhid)
        newD = jnp.where(contained, D | (1 << dsp), D)
        valid = ~disjoint
        newD = jnp.where(covered, D, newD)
        valid = jnp.where(covered, True, valid)
        return expand & valid, newD

    cl, cr = di.left[node], di.right[node]
    vl, Dl = child(cl)
    vr, Dr = child(cr)
    C2 = 2 * fnode.shape[0]
    return (node, do_scan, jnp.stack([cl, cr], axis=1).reshape(C2),
            jnp.stack([Dl, Dr], axis=1).reshape(C2),
            jnp.stack([vl, vr], axis=1).reshape(C2))


def _sweep(di, qlo, qhi, p, visit: Callable, acc0):
    """The level sweep shared by the three level routers (module
    docstring): a ``lax.scan`` over the H tree levels, each a
    ``while_loop`` over the live prefix ``[0, live)`` of the frontier in
    chunks of C = min(_CHUNK, frontier_cap) slots. A chunk classifies
    its slots, calls ``visit(node (C,), do_scan (C,), acc) -> acc`` and
    compacts its children into the next frontier at a running offset
    plus the chunk's own exclusive cumsum: the slot the level-wide
    cumsum gives, so the next frontier, and its overflow clamp at
    ``frontier_cap``, are those of a whole-frontier sweep.

    Returns (acc, (H,) int32 chunks each level ran for this lane); under
    ``vmap`` a level's loop runs to the batch's largest count, which
    ``engine.router_chunks`` reads back."""
    F = p.frontier_cap
    _require_frontier(F)
    m = di.attrs.shape[1]
    full = (1 << m) - 1
    H = di.nbrs.shape[1]          # tree levels == path height (tree.py)
    C = min(_CHUNK, F)
    Fp = -(-F // C) * C           # whole chunks: a chunk slice never clamps
    slot = jnp.arange(C)

    def level(st, _):
        fnode, fD, live, gnode, gD, acc = st
        nc = (live + C - 1) // C

        def more(c):
            return c[0] < nc

        def chunk(c):
            i, gnode, gD, nxt, acc = c
            base = i * C
            node, do_scan, cn, cD, cv = _frontier_step(
                di, qlo, qhi, full,
                jax.lax.dynamic_slice(fnode, (base,), (C,)),
                jax.lax.dynamic_slice(fD, (base,), (C,)),
                base + slot < live)
            pos = nxt + jnp.cumsum(cv) - cv              # exclusive
            dst = jnp.where(cv & (pos < F), pos, Fp)     # Fp: dropped (clamp)
            gnode = gnode.at[dst].set(cn, mode="drop")
            gD = gD.at[dst].set(cD, mode="drop")
            return (i + 1, gnode, gD, nxt + jnp.sum(cv),
                    visit(node, do_scan, acc))

        _, gnode, gD, nxt, acc = jax.lax.while_loop(
            more, chunk, (jnp.int32(0), gnode, gD, jnp.int32(0), acc))
        # the next level reads gnode; this level's buffers take its
        # children in turn
        return (gnode, gD, jnp.minimum(nxt, F), fnode, fD, acc), nc

    fnode = jnp.zeros((Fp,), jnp.int32).at[0].set(di.root)
    fD = jnp.zeros((Fp,), jnp.int32).at[0].set(_root_D0(di, qlo, qhi, m))
    st, chunks = jax.lax.scan(
        level, (fnode, fD, jnp.int32(1), jnp.zeros_like(fnode),
                jnp.zeros_like(fD), acc0), None, length=H)
    return st[5], chunks


def route_level_sync(di, qlo: jax.Array, qhi: jax.Array, p):
    """Returns (entry-point object ids (c_e,), -1 padded, DFS order;
    () int32 in-range cardinality bound — the full-antichain count sum,
    module docstring; (H,) int32 chunks per level). The DFS-rank key
    makes the two routers' entry vectors agree."""
    _require_frontier(p.frontier_cap)
    n = di.order.shape[0]
    SB = p.scan_budget
    W = min(SB, _SCAN_STEP)
    order_pad = jnp.pad(di.order, (0, W))
    lane = jnp.arange(W)

    def first_in_box(sj, cj):
        """(T,) first object of each node, in DFS order, that lies in the
        box (-1: none among its first min(count, scan_budget)) — read W
        objects at a time until every node has its hit or runs out."""
        lim = jnp.minimum(cj, SB)

        def more(c):
            off, e = c
            return jnp.any((e < 0) & (off < lim))

        def step(c):
            off, e = c
            pos = off + lane                                # (W,)
            win = order_pad[sj[:, None] + pos[None, :]]     # (T, W)
            a = di.attrs[win]                               # (T, W, m)
            ok = ((pos[None, :] < lim[:, None])
                  & jnp.all((a >= qlo) & (a <= qhi), axis=-1))
            hit = jnp.take_along_axis(
                win, jnp.argmax(ok, axis=1)[:, None], axis=1)[:, 0]
            return off + W, jnp.where((e < 0) & ok.any(axis=1), hit, e)

        return jax.lax.while_loop(
            more, step, (jnp.int32(0), jnp.full(sj.shape, -1, jnp.int32)))[1]

    def visit(node, do_scan, acc):
        keys, ents, card = acc
        s = di.start[node]
        cnt = di.count[node]
        card = card + jnp.sum(jnp.where(do_scan, cnt, 0))
        # DFS-rank keys of the chunk's scanned nodes: right-first
        # pre-order over the scanned antichain == descending range end
        # (module docstring)
        cand = jnp.where(do_scan, n - (s + cnt), _I32_MAX)
        T = min(cand.shape[0], p.c_e)      # nodes entry-scanned per step

        # ---- entry scans in key order, T nodes at a time, while a
        # scanned node could still enter the running c_e smallest keys:
        # the same entries as scanning the whole sweep at once
        def pending(c):
            cand, keys, _ = c
            return jnp.any(cand < keys[-1])

        def scan_step(c):
            cand, keys, ents = c
            neg, j = jax.lax.top_k(-cand, T)
            live = -neg < keys[-1]
            e = first_in_box(s[j], jnp.where(live, cnt[j], 0))
            key = jnp.where(e >= 0, -neg, _I32_MAX)
            allk = jnp.concatenate([keys, key])
            alle = jnp.concatenate([ents, e])
            srt = jnp.argsort(allk, stable=True)[: p.c_e]
            return cand.at[j].set(_I32_MAX), allk[srt], alle[srt]

        _, keys, ents = jax.lax.while_loop(pending, scan_step,
                                           (cand, keys, ents))
        return keys, ents, card

    (_, ents, card), chunks = _sweep(
        di, qlo, qhi, p, visit,
        (jnp.full((p.c_e,), _I32_MAX, jnp.int32),
         jnp.full((p.c_e,), -1, jnp.int32), jnp.int32(0)))
    return ents, card, chunks


def route_level_card(di, qlo: jax.Array, qhi: jax.Array, p) -> jax.Array:
    """Estimate-only sweep: the () int32 in-range cardinality bound of
    ``route_level_sync`` without the entry scans — same traversal, same
    ``frontier_cap`` contract, but no entry-scan gathers, so the
    planner's plan pass costs a fraction of a full route (DESIGN.md
    §10)."""
    def visit(node, do_scan, card):
        return card + jnp.sum(jnp.where(do_scan, di.count[node], 0))

    return _sweep(di, qlo, qhi, p, visit, jnp.int32(0))[0]


def route_level_windows(di, qlo: jax.Array, qhi: jax.Array, p, *,
                        node_thr: int, W: int):
    """Estimate sweep + per-node hybrid classification, device-side
    (DESIGN.md §14): the ``route_level_card`` traversal, additionally
    splitting the scanned antichain by RAW node count into small
    (0 < count <= node_thr) and large nodes, and collecting the small
    nodes' DFS extents into a fixed-width window buffer.

    Returns (card () int32, n_small () int32, n_large () int32,
    starts (W,) int32, counts (W,) int32) — windows sorted ascending by
    start (the windowed kernel's contract, engine._build_windows), pad
    slots (-1, 0). ``W`` must bound the per-query small-antichain size;
    the collective caller derives it from static index counts (every
    window has count >= 1 and windows are DFS-disjoint), so the
    overflow clamp below is unreachable there."""
    def visit(node, do_scan, acc):
        card, n_small, n_large, wstart, wcount, fill = acc
        cnt = di.count[node]
        small = do_scan & (cnt > 0) & (cnt <= node_thr)
        large = do_scan & (cnt > node_thr)
        pos = fill + jnp.cumsum(small) - small          # exclusive
        slot = jnp.where(small, jnp.minimum(pos, W), W)  # W: drop (clamp)
        wstart = wstart.at[slot].set(di.start[node], mode="drop")
        wcount = wcount.at[slot].set(cnt, mode="drop")
        return (card + jnp.sum(jnp.where(do_scan, cnt, 0)),
                n_small + jnp.sum(small), n_large + jnp.sum(large),
                wstart, wcount, jnp.minimum(fill + jnp.sum(small), W))

    zero = jnp.int32(0)
    card, n_small, n_large, wstart, wcount, _ = _sweep(
        di, qlo, qhi, p, visit,
        (zero, zero, zero,
         jnp.full((W,), _I32_MAX, jnp.int32),   # i32max pads sort last
         jnp.zeros((W,), jnp.int32), zero))[0]
    # antichain extents are disjoint -> starts unique among real windows;
    # stable ascending sort puts the i32max pads last
    o = jnp.argsort(wstart, stable=True)
    wstart, wcount = wstart[o], wcount[o]
    wstart = jnp.where(wcount > 0, wstart, -1)
    return card, n_small, n_large, wstart, wcount


class HostCardEstimator:
    """Vectorized host form of the routing cardinality bound — the
    planner's plan-pass workhorse (DESIGN.md §10).

    Same quantity as ``route_level_card`` and the python twin
    ``query_ref.estimate_cardinality`` (three-way pinned by
    tests/test_planner.py), computed **node-parallel** instead of
    frontier-sequential. The rewrite rests on two path monotonicities of
    the tree: BL masks only grow (``bl[child] ⊇ bl[parent]`` — asserted
    by ``tree.validate``) and a dim's rectangle projection only shrinks,
    so the traversal's incrementally-maintained D equals the closed form
    ``D(p) = bl[p] | {i: proj_i(R(p)) ⊆ box_i}`` at every node. That
    turns the sweep into dense (B, P) numpy passes — D / stop / edge
    masks for all nodes at once, then one level-ordered reachability
    propagation (each node touched exactly once) — with none of the
    per-level gather/scatter traffic that makes the device frontier form
    expensive off-TPU. The plan decision is host-side even in TPU
    serving, so this is the form ``engine.Planner`` dispatches on.

    Built once per index/shard from host copies of the flattened tree;
    ``cards((B, m) qlo, (B, m) qhi) -> (B,) int64``.
    """

    def __init__(self, left, right, dim, bl, lo, hi, count, root: int):
        P, m = lo.shape
        self.m = int(m)
        self.full = (1 << m) - 1
        self.bl = bl.astype(np.int64)
        self.lo, self.hi = lo, hi
        self.count = count.astype(np.int64)
        self.is_leaf = left < 0
        self.root = int(root)
        # parent pointers + levels via one host BFS (DeviceIndex drops
        # the tree's parent array; rebuilding it is O(P))
        parent = np.full(P, -1, np.int64)
        for child in (left, right):
            src = np.nonzero(child >= 0)[0]
            parent[child[src]] = src
        self.parent = parent
        level = np.full(P, -1, np.int64)
        level[self.root] = 0
        frontier = np.asarray([self.root])
        levels = [frontier]
        while True:
            children = np.concatenate([left[frontier], right[frontier]])
            frontier = children[children >= 0]
            if not frontier.size:
                break
            level[frontier] = len(levels)
            levels.append(frontier)
        self.levels = levels
        # static per-node edge data: the parent's split dim and this
        # node's rectangle bounds on it (what the push's disjoint check
        # reads)
        ps = np.where(parent >= 0, dim[np.maximum(parent, 0)], 0)
        self.ps = ps.astype(np.int64)
        self.plo = lo[np.arange(P), ps]
        self.phi = hi[np.arange(P), ps]

    def antichain(self, qlo: np.ndarray, qhi: np.ndarray) -> np.ndarray:
        """(B, m) boxes -> (B, P) bool: the per-query scanned antichain —
        exactly the nodes the level sweep stops at (stop & reached).
        Every in-range object lies in exactly one antichain node, and
        the nodes' ``[start, start + count)`` DFS ranges are disjoint —
        the hybrid planner's per-node dispatch set (DESIGN.md §12);
        ``cards`` is its count-weighted row sum."""
        B = qlo.shape[0]
        P = self.parent.shape[0]
        pa = np.maximum(self.parent, 0)
        # closed-form D for every node at once (class docstring)
        D = np.broadcast_to(self.bl, (B, P)).copy()
        for i in range(self.m):
            D |= ((self.lo[:, i] >= qlo[:, i, None])
                  & (self.hi[:, i] <= qhi[:, i, None])).astype(np.int64) << i
        stop = (D == self.full) | self.is_leaf
        # edge survival: pushed unless the parent's split dim is
        # uncovered AND this node's projection on it misses the box
        disjoint = ((self.plo > qhi[:, self.ps])
                    | (self.phi < qlo[:, self.ps]))
        edge_ok = (((D[np.arange(B)[:, None], pa] >> self.ps) & 1) > 0) \
            | ~disjoint
        # level-ordered reachability: each node reads its parent once
        reached = np.zeros((B, P), bool)
        reached[:, self.root] = True
        for nl in self.levels[1:]:
            pl = self.parent[nl]
            reached[:, nl] = (reached[:, pl] & ~stop[:, pl]
                              & edge_ok[:, nl])
        return stop & reached

    def cards(self, qlo: np.ndarray, qhi: np.ndarray) -> np.ndarray:
        return self.antichain(qlo, qhi) @ self.count


def deleted_per_node(order: np.ndarray, start: np.ndarray,
                     count: np.ndarray, deleted_rows: np.ndarray
                     ) -> np.ndarray:
    """Per-node tombstone counts for the streaming planner (DESIGN.md §11):
    how many of ``deleted_rows`` (internal object ids) fall inside each
    node's object range ``order[start : start+count]``.

    Subtracting this from ``count`` keeps the routing cardinality bound
    an upper bound on *live* in-range objects, so deleted rows cannot
    inflate the planner's dispatch estimates. O(n + P) — one inverse
    permutation + one prefix sum over a 0/1 mark array; node ranges are
    contiguous in ``order`` position space by construction (tree.py).

    ``order`` must be the REAL slice (``order[:n]``): padded slots hold 0
    and would corrupt the inverse permutation.
    """
    n = order.shape[0]
    deleted_rows = np.asarray(deleted_rows, np.int64)
    if not deleted_rows.size:
        return np.zeros(start.shape[0], np.int64)
    inv = np.empty(n, np.int64)
    inv[np.asarray(order, np.int64)] = np.arange(n)
    mark = np.zeros(n + 1, np.int64)
    mark[inv[deleted_rows] + 1] = 1
    cum = np.cumsum(mark)
    s = start.astype(np.int64)
    e = np.minimum(s + count.astype(np.int64), n)   # padded nodes -> 0
    return cum[e] - cum[np.minimum(s, n)]


def required_frontier_cap(di) -> int:
    """Smallest frontier width that can never drop a branch: the max node
    count over tree levels (per shard for a stacked DeviceIndex). The
    frontier at sweep step l holds a subset of the level-l nodes, so this
    bound is sufficient for every query. Vectorized per level — O(height)
    numpy ops, not O(num_nodes) Python iterations (this runs inside
    validate_search_params on every index install/hot-swap)."""
    left = np.asarray(jax.device_get(di.left))
    right = np.asarray(jax.device_get(di.right))
    root = np.asarray(jax.device_get(di.root))
    if left.ndim == 1:
        left, right, root = left[None], right[None], root[None]
    cap = 1
    for s in range(left.shape[0]):
        frontier = np.asarray([root[s]], dtype=np.int64)
        while frontier.size:
            cap = max(cap, int(frontier.size))
            children = np.concatenate([left[s][frontier],
                                       right[s][frontier]])
            frontier = children[children >= 0]
    return cap


def resolve_router(name: str) -> Callable:
    """Router name -> route(di, qlo, qhi, p) -> (entries, card, chunks)
    (the Phase-A contract)."""
    if name == "level":
        return route_level_sync
    if name == "dfs":
        return route_dfs
    raise ValueError(f"unknown router {name!r}; expected one of {ROUTERS}")
