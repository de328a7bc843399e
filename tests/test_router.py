"""Phase-A router correctness (DESIGN.md §9): the level-synchronous batched
router must return the SAME entry vectors as the stack DFS — device vs
device, device vs numpy twin, twin vs twin — including on adversarial
attribute distributions (cardinality-1 dims, fully duplicated tuples,
zero-selectivity predicates), plus the frontier_cap validation contract."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import engine as eng
from repro.core import query_ref as qr
from repro.core import router as rt
from repro.core.khi import KHIConfig, KHIIndex
from repro.core.util import pow2_at_least
from repro.data import make_queries


def _route_all(index, preds, c_e=10):
    """Run all four router implementations over the predicates; returns
    {name: [entry list per predicate]} with device outputs un-padded."""
    di = eng.device_put_index(index)
    p = eng.derive_search_params(
        eng.SearchParams(k=10, ef=32, c_e=c_e, c_n=16), di)
    out = {"host_dfs": [], "host_level": [], "dev_dfs": [], "dev_level": []}
    for pr in preds:
        qlo, qhi = jnp.asarray(pr.lo), jnp.asarray(pr.hi)
        out["host_dfs"].append(qr.range_filter(index, pr, c_e))
        out["host_level"].append(qr.range_filter_level(index, pr, c_e))
        for name, fn in (("dev_dfs", rt.route_dfs),
                         ("dev_level", rt.route_level_sync)):
            e, _card, _counts = fn(di, qlo, qhi, p)
            out[name].append([int(x) for x in np.asarray(e) if x >= 0])
    return out


def _assert_all_equal(routes, context=""):
    ref = routes["host_dfs"]
    for name in ("host_level", "dev_dfs", "dev_level"):
        for i, (a, b) in enumerate(zip(ref, routes[name])):
            assert a == b, f"{context} pred {i}: host_dfs={a} {name}={b}"


# ------------------------------------------------------ tier-1 workload

def test_routers_agree_tier1(tiny_index, tiny_queries):
    """All four router implementations return identical entry lists (set
    AND order) on the tier-1 workload."""
    _, preds = tiny_queries
    _assert_all_equal(_route_all(tiny_index, preds), "tier1")


@pytest.mark.parametrize("step", [1, 3, 8])
def test_level_router_entry_scan_steps(tiny_index, tiny_queries,
                                       monkeypatch, step):
    """The level router reads each scanned node ``_SCAN_STEP`` objects at
    a time; a step far below the nodes' sizes (several steps per node)
    still finds the same entries and cardinality as the DFS router's
    whole-window scan."""
    _, preds = tiny_queries
    di = eng.device_put_index(tiny_index)
    p = eng.derive_search_params(
        eng.SearchParams(k=10, ef=32, c_e=10, c_n=16), di)
    assert p.scan_budget > step
    monkeypatch.setattr(rt, "_SCAN_STEP", step)
    for pr in preds[:8]:
        qlo, qhi = jnp.asarray(pr.lo), jnp.asarray(pr.hi)
        e_lvl, card, _ = rt.route_level_sync(di, qlo, qhi, p)
        e_dfs, _, _ = rt.route_dfs(di, qlo, qhi, p)
        np.testing.assert_array_equal(np.asarray(e_lvl), np.asarray(e_dfs))
        assert int(card) == int(rt.route_level_card(di, qlo, qhi, p))


def _boxes(preds):
    return (jnp.asarray(np.stack([pr.lo for pr in preds]), jnp.float32),
            jnp.asarray(np.stack([pr.hi for pr in preds]), jnp.float32))


def _live_expected(index, preds, F, H):
    """(B, H) the host twin's live frontier width per level for each
    lane, 0 past its last level."""
    out = np.zeros((len(preds), H), np.int32)
    for i, pr in enumerate(preds):
        w = qr.frontier_widths(index, pr, F)
        out[i, :len(w)] = w
    return out


def _chunks_expected(index, preds, F, C, H):
    """(B, H) chunks per level the sweep owes each lane: ceil(w / C) of
    the host twin's live frontier width w."""
    return -(-_live_expected(index, preds, F, H) // C)


def _route_batch(di, p, preds, node_thr):
    """The three level routers and the DFS, vmapped over one batch."""
    qlo, qhi = _boxes(preds)
    W = pow2_at_least(int(di.start.shape[0]))
    run = jax.jit(jax.vmap(lambda lo, hi: (
        rt.route_level_sync(di, lo, hi, p),
        rt.route_dfs(di, lo, hi, p)[0],
        rt.route_level_card(di, lo, hi, p),
        rt.route_level_windows(di, lo, hi, p, node_thr=node_thr, W=W))))
    return jax.tree.map(np.asarray, run(qlo, qhi))


def _check_windows(index, preds, windows, node_thr):
    """route_level_windows against the host planner's antichain split."""
    qlo, qhi = (np.asarray(x) for x in _boxes(preds))
    planner = eng.Planner(index, eng.SearchParams(
        k=8, strategy="hybrid", node_scan_threshold=node_thr))
    plan = planner.plan(qlo, qhi)
    card, n_small, n_large, wstarts, wcounts = windows
    np.testing.assert_array_equal(card, plan.card)
    np.testing.assert_array_equal(n_small, plan.n_windows)
    anti = planner._estimators[0].antichain(qlo, qhi)
    np.testing.assert_array_equal(
        n_large, (anti & (planner._node_count[0] > node_thr)).sum(axis=1))
    start, count = index.tree.start, index.tree.count
    for b in range(len(preds)):
        nodes = np.nonzero(plan.small_nodes[0][b])[0]
        exp = sorted((int(start[n]), int(count[n])) for n in nodes)
        got = [(int(s), int(c)) for s, c in zip(wstarts[b], wcounts[b])
               if s >= 0]
        assert got == exp, f"query {b}: windows {got} != host {exp}"


@pytest.mark.parametrize("chunk", [1, 3, 8, None])
def test_level_router_chunk_widths(tiny_index, tiny_queries, monkeypatch,
                                   chunk):
    """The level sweep reads its live frontier ``_CHUNK`` slots at a
    time (None: a chunk at least ``frontier_cap`` wide, one per level).
    Chunks far narrower than the frontier (many per level) leave the
    entries, the cardinality bound and the hybrid windows as the DFS
    router and the host twins give them, and each level runs
    ceil(live / C) chunks of the twin's live frontier."""
    _, preds = tiny_queries
    di = eng.device_put_index(tiny_index)
    p = eng.derive_search_params(
        eng.SearchParams(k=10, ef=32, c_e=10, c_n=16), di)
    F, H = p.frontier_cap, di.nbrs.shape[1]
    C = F if chunk is None else chunk
    assert F > 8 * 4
    monkeypatch.setattr(rt, "_CHUNK", 2 * F if chunk is None else chunk)
    (ents, card, counts), e_dfs, card_only, windows = _route_batch(
        di, p, preds, node_thr=64)
    chunks = counts.chunks
    np.testing.assert_array_equal(ents, e_dfs)
    np.testing.assert_array_equal(counts.live,
                                  _live_expected(tiny_index, preds, F, H))
    for i, pr in enumerate(preds):
        assert [int(x) for x in ents[i] if x >= 0] == \
            qr.range_filter_level(tiny_index, pr, p.c_e)
        assert card[i] == card_only[i] == \
            qr.estimate_cardinality(tiny_index, pr)
    np.testing.assert_array_equal(
        chunks, _chunks_expected(tiny_index, preds, F, C, H))
    if chunk is not None:
        assert chunks.max() > 2          # a level spans several chunks
    _check_windows(tiny_index, preds, windows, node_thr=64)


def test_level_router_mixed_lane_widths(tiny_index, tiny_data, tiny_queries,
                                        monkeypatch):
    """One vmapped batch whose lanes' live frontiers differ by chunks: an
    empty box, narrow boxes, a box holding every row (the root is
    covered, no level below it) and a box cut just inside every
    attribute's range (the widest frontier). Each lane's entries and
    bound are those it gets routed alone; the batch's chunk loop runs to
    its widest lane, which is what ``engine.level_lockstep`` counts."""
    _, attrs = tiny_data
    _, preds = tiny_queries
    m = tiny_index.m
    q = np.quantile(attrs, [0.02, 0.98], axis=0).astype(np.float32)
    lanes = [qr.Predicate(np.full(m, np.inf, np.float32),
                          np.full(m, -np.inf, np.float32)),
             preds[0],
             qr.Predicate(np.full(m, -np.inf, np.float32),
                          np.full(m, np.inf, np.float32)),
             qr.Predicate(q[0], q[1]),
             preds[1]]
    di = eng.device_put_index(tiny_index)
    p = eng.derive_search_params(
        eng.SearchParams(k=10, ef=32, c_e=10, c_n=16), di)
    F, H = p.frontier_cap, di.nbrs.shape[1]
    monkeypatch.setattr(rt, "_CHUNK", 3)
    (ents, card, counts), e_dfs, card_only, windows = _route_batch(
        di, p, lanes, node_thr=64)
    chunks = counts.chunks
    np.testing.assert_array_equal(ents, e_dfs)
    np.testing.assert_array_equal(card, card_only)
    exp = _chunks_expected(tiny_index, lanes, F, 3, H)
    np.testing.assert_array_equal(chunks, exp)
    total = exp.sum(axis=1)
    assert total[0] == 1 and total[2] == 1      # the root's level alone
    assert total[3] > 2 * max(total[1], total[4]) > 0
    assert int(eng.level_lockstep(jnp.asarray(chunks))) == \
        exp.max(axis=0).sum()
    for i, pr in enumerate(lanes):
        e1, c1, ch1 = rt.route_level_sync(di, jnp.asarray(pr.lo),
                                          jnp.asarray(pr.hi), p)
        np.testing.assert_array_equal(ents[i], np.asarray(e1))
        assert card[i] == int(c1) == qr.estimate_cardinality(tiny_index, pr)
        np.testing.assert_array_equal(chunks[i], np.asarray(ch1.chunks))
    assert (ents[0] < 0).all() and card[0] == 0
    assert card[2] == tiny_index.n
    _check_windows(tiny_index, lanes, windows, node_thr=64)


def test_level_router_is_engine_default(tiny_index, tiny_queries):
    """The engine's default params route through the level-sync sweep and
    still match the DFS engine bit-for-bit."""
    Q, preds = tiny_queries
    base = dict(k=10, ef=32, c_e=10, c_n=16)
    ids_l, d_l, h_l = eng.search_batch(tiny_index, Q, preds,
                                       eng.SearchParams(**base))
    ids_d, d_d, h_d = eng.search_batch(
        tiny_index, Q, preds, eng.SearchParams(router="dfs", **base))
    assert eng.SearchParams().router == "level"
    np.testing.assert_array_equal(ids_l, ids_d)
    np.testing.assert_array_equal(h_l, h_d)
    np.testing.assert_array_equal(d_l, d_d)


def _router_widths(txt):
    """Every dim of each gather output, scatter update and sort operand
    the compiled program runs in the router's scope."""
    import re
    shapes = dict(re.findall(r"%([\w.\-]+) = ([^ ]+) ", txt))
    dims = lambda s: {int(x) for x in re.findall(r"\d+", s.split("{")[0])}
    out = set()
    for line in txt.splitlines():
        m = re.search(r"= ([^ ]+) (gather|scatter|sort)\((.*?)\)", line)
        if not m or "khi_router" not in line:
            continue
        shape, op, operands = m.groups()
        names = [o.strip().lstrip("%") for o in operands.split(",")]
        out |= (dims(shape) if op == "gather" else set().union(
            *(dims(shapes.get(o, "")) for o in
              (names[2:] if op == "scatter" else names))))
    return out


@pytest.mark.parametrize("chunk", [11, None])
def test_router_hlo_sweeps_no_frontier_wide_op(tiny_index, monkeypatch,
                                               chunk):
    """In the compiled graph program no gather, scatter or sort of the
    router spans the frontier (its frontier_cap slots, or the whole
    chunks that hold them, per lane or for the batch): each works on one
    chunk of C = 11 slots. With one chunk per level (None: C >=
    frontier_cap) the chunk is the frontier and the check finds it."""
    from repro.serve import KHIService
    monkeypatch.setattr(rt, "_CHUNK", chunk or 1 << 20)
    svc = KHIService(tiny_index, eng.SearchParams(k=10, ef=32, c_n=16,
                                                  strategy="graph"))
    F, B = svc.params.frontier_cap, 8
    wide = {x * f for x in (1, 2, B, 2 * B) for f in (F, -(-F // 11) * 11)}
    found = _router_widths(svc.compiled_hlo(B)["graph"]) & wide
    assert bool(found) == (chunk is None), found


# ------------------------------------- adversarial attribute distributions

def _rand_vecs(n, d=16, seed=0):
    return np.random.default_rng(seed).standard_normal((n, d)).astype(
        np.float32)


def test_routers_cardinality_one_dimension():
    """A constant attribute column: every split on it is maximally skewed,
    so the builder blacklists it everywhere and routing must still find
    entries through the leaf fallback / BL-covered scans."""
    rng = np.random.default_rng(3)
    n = 400
    attrs = np.stack([np.full(n, 7.0, np.float32),
                      rng.uniform(0, 100, n).astype(np.float32),
                      rng.integers(0, 5, n).astype(np.float32)], axis=1)
    index = KHIIndex.build(_rand_vecs(n), attrs, KHIConfig(M=8))
    _, preds = make_queries(index.vecs, attrs, n_queries=12, sigma=1 / 8,
                            seed=4)
    # include predicates that pin / exclude the constant dim explicitly
    preds += [qr.Predicate.from_bounds(3, {0: (7.0, 7.0)}),
              qr.Predicate.from_bounds(3, {0: (6.0, 6.5)}),
              qr.Predicate.from_bounds(3, {0: (0.0, 10.0), 1: (10.0, 40.0)})]
    routes = _route_all(index, preds)
    _assert_all_equal(routes, "card1")
    assert any(len(e) > 0 for e in routes["host_dfs"])


def test_routers_duplicated_tuples():
    """Fully duplicated attribute tuples: every candidate split fails the
    skew check, the root degenerates to a scannable node, and the scan
    budget must cover it (derive_search_params guarantees that)."""
    n = 120
    attrs = np.tile(np.asarray([[1.0, 2.0, 3.0]], np.float32), (n, 1))
    index = KHIIndex.build(_rand_vecs(n, seed=5), attrs, KHIConfig(M=8))
    preds = [qr.Predicate.from_bounds(3, {}),
             qr.Predicate.from_bounds(3, {0: (1.0, 1.0)}),
             qr.Predicate.from_bounds(3, {0: (0.0, 0.5)}),   # excludes all
             qr.Predicate.from_bounds(3, {1: (2.0, 9.0), 2: (3.0, 3.0)})]
    routes = _route_all(index, preds)
    _assert_all_equal(routes, "dup")
    assert routes["host_dfs"][2] == []          # zero-selectivity
    assert len(routes["host_dfs"][1]) >= 1


def test_routers_few_distinct_tuples():
    """A handful of distinct tuples, each heavily duplicated: splits
    alternate between accepted and blacklisted dims."""
    rng = np.random.default_rng(11)
    base = np.asarray([[0, 0], [0, 1], [5, 1], [5, 9]], np.float32)
    attrs = base[rng.integers(0, 4, 500)]
    index = KHIIndex.build(_rand_vecs(500, seed=6), attrs, KHIConfig(M=8))
    preds = [qr.Predicate.from_bounds(2, {0: (0.0, 0.0)}),
             qr.Predicate.from_bounds(2, {0: (5.0, 5.0), 1: (9.0, 9.0)}),
             qr.Predicate.from_bounds(2, {1: (1.0, 1.0)}),
             qr.Predicate.from_bounds(2, {0: (1.0, 4.0)}),   # gap: empty
             qr.Predicate.from_bounds(2, {})]
    routes = _route_all(index, preds)
    _assert_all_equal(routes, "few-distinct")
    assert routes["host_dfs"][3] == []


def test_routers_zero_selectivity(tiny_index):
    """Empty ranges (lo > hi, the service's pad-lane encoding) and
    out-of-domain windows return zero entries from every router."""
    m = tiny_index.m
    empty = qr.Predicate(np.full(m, np.inf, np.float32),
                         np.full(m, -np.inf, np.float32))
    far = qr.Predicate.from_bounds(m, {0: (1e9, 2e9)})
    routes = _route_all(tiny_index, [empty, far])
    for name, ents in routes.items():
        assert ents == [[], []], name


# ------------------------------------------------------------- validation

def test_frontier_cap_validation(tiny_index):
    """Undersized frontier_cap must raise (or auto-raise) like scan_budget:
    a silently clamped frontier drops router branches."""
    di = eng.device_put_index(tiny_index)
    need = eng.required_frontier_cap(di)
    assert need > 1
    small = eng.derive_search_params(eng.SearchParams(), di)
    small = eng.SearchParams(scan_budget=small.scan_budget,
                             stack_cap=small.stack_cap, frontier_cap=2)
    with pytest.raises(ValueError, match="frontier_cap"):
        eng.validate_search_params(small, di)
    adj = eng.validate_search_params(small, di, on_undersized="adjust")
    assert adj.frontier_cap == need
    # the DFS router does not use the frontier: no frontier_cap complaint
    import dataclasses
    dfs = dataclasses.replace(small, router="dfs")
    assert eng.validate_search_params(dfs, di) is dfs


def test_frontier_cap_truncation_is_clamped(tiny_index, tiny_queries):
    """An explicitly undersized frontier (on_undersized='ignore') must not
    crash — branches drop at the clamp, mirroring the DFS stack_cap
    contract."""
    Q, preds = tiny_queries
    p = eng.SearchParams(k=10, ef=32, c_e=10, c_n=16, frontier_cap=2)
    ids, dists, hops = eng.search_batch(tiny_index, Q[:4], preds[:4], p,
                                        on_undersized="ignore")
    for i, pr in enumerate(preds[:4]):
        got = [x for x in ids[i].tolist() if x >= 0]
        assert all(pr.matches(tiny_index.attrs[g]) for g in got)


@pytest.mark.parametrize("chunk", [1, 2, 3])
def test_frontier_cap_clamp_identical_across_chunks(tiny_index, tiny_queries,
                                                   monkeypatch, chunk):
    """An undersized frontier (on_undersized='ignore') clamps at
    frontier_cap as the whole-frontier sweep does, whatever the chunk
    width: a chunk's children take the slots the level-wide cumsum gives
    them, so the same branches drop, and the entries, the bound, the
    windows and the live widths equal a one-chunk-per-level sweep's and
    the host twin's clamped replay."""
    _, preds = tiny_queries
    di = eng.device_put_index(tiny_index)
    full = eng.derive_search_params(
        eng.SearchParams(k=10, ef=32, c_e=10, c_n=16), di)
    F = 5
    p = eng.SearchParams(k=10, ef=32, c_e=10, c_n=16, frontier_cap=F,
                         scan_budget=full.scan_budget)
    assert chunk < F < full.frontier_cap
    H = di.nbrs.shape[1]
    monkeypatch.setattr(rt, "_CHUNK", F)
    (e_w, card_w, counts_w), _, card_only_w, win_w = _route_batch(
        di, p, preds, node_thr=64)
    monkeypatch.setattr(rt, "_CHUNK", chunk)
    (e_c, card_c, counts_c), _, card_only_c, win_c = _route_batch(
        di, p, preds, node_thr=64)
    np.testing.assert_array_equal(e_c, e_w)
    np.testing.assert_array_equal(card_c, card_w)
    np.testing.assert_array_equal(card_only_c, card_only_w)
    for a, b in zip(win_c, win_w):
        np.testing.assert_array_equal(a, b)
    for got, C in ((counts_w, F), (counts_c, chunk)):
        np.testing.assert_array_equal(
            got.chunks, _chunks_expected(tiny_index, preds, F, C, H))
        np.testing.assert_array_equal(
            got.live, _live_expected(tiny_index, preds, F, H))
    twin = [qr._level_sweep(tiny_index, pr, F) for pr in preds]
    assert any(max(w) == F for w, _ in twin)             # the clamp bites
    np.testing.assert_array_equal(card_c, [c for _, c in twin])
    assert (card_c < [qr.estimate_cardinality(tiny_index, pr)
                      for pr in preds]).any()


def test_unknown_router_rejected():
    with pytest.raises(ValueError, match="router"):
        eng.SearchParams(router="bfs")
    with pytest.raises(ValueError, match="router"):
        rt.resolve_router("astar")
    with pytest.raises(ValueError, match="frontier_cap"):
        eng.SearchParams(frontier_cap=-1)
    # 0 is the "derive from the index" sentinel: constructible, but
    # routing with it unresolved raises instead of silently truncating
    di_less = eng.SearchParams(frontier_cap=0)
    import jax.numpy as jnp
    with pytest.raises(ValueError, match="frontier_cap"):
        rt.route_level_sync(None, jnp.zeros(3), jnp.zeros(3), di_less)


def test_c_e_validation():
    """Satellite: c_e > ef would seed entries past the beam — reject."""
    with pytest.raises(ValueError, match="c_e"):
        eng.SearchParams(ef=8, c_e=9)
    assert eng.SearchParams(ef=8, c_e=8).c_e == 8
    # expand_width <= ef stays enforced alongside it
    with pytest.raises(ValueError, match="expand_width"):
        eng.SearchParams(ef=8, expand_width=9)


def test_required_frontier_cap_sharded(tiny_data):
    """The frontier bound sees through the shard-stacked layout."""
    from repro.core.sharded import build_sharded
    vecs, attrs = tiny_data
    skhi = build_sharded(vecs, attrs, 2, KHIConfig(M=16, builder="device"))
    need = eng.required_frontier_cap(skhi.di)
    assert need >= 1
    adj = eng.validate_search_params(eng.SearchParams(frontier_cap=1),
                                     skhi.di, on_undersized="adjust")
    assert adj.frontier_cap >= need
