"""Phase-A router correctness (DESIGN.md §9): the level-synchronous batched
router must return the SAME entry vectors as the stack DFS — device vs
device, device vs numpy twin, twin vs twin — including on adversarial
attribute distributions (cardinality-1 dims, fully duplicated tuples,
zero-selectivity predicates), plus the frontier_cap validation contract."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import engine as eng
from repro.core import query_ref as qr
from repro.core import router as rt
from repro.core.khi import KHIConfig, KHIIndex
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
            e, _card = fn(di, qlo, qhi, p)
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
        e_lvl, card = rt.route_level_sync(di, qlo, qhi, p)
        e_dfs, _ = rt.route_dfs(di, qlo, qhi, p)
        np.testing.assert_array_equal(np.asarray(e_lvl), np.asarray(e_dfs))
        assert int(card) == int(rt.route_level_card(di, qlo, qhi, p))


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
