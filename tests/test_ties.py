"""The LAION deployment's tie-heavy attributes (DESIGN.md §6, §9): two
zipf attributes whose most common value holds about half the rows, so
the tree blacklists every dim of some nodes and leaves them holding more
than ``leaf_capacity`` rows, and a split at the tied value hands its
right child a rectangle that starts at a value none of its rows holds.
The data is ``repro.data``'s ``laion`` preset cut to 8,192 rows of
width 32 (at 4,096 rows the tree has no such fat leaf)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as eng
from repro.core import query_ref as qr
from repro.core import router as rt
from repro.core.khi import KHIConfig, KHIIndex
from repro.data import DATASET_PRESETS, make_dataset, make_queries
from repro.serve import KHIService, ServeConfig

N, D, B, K = 8192, 32, 8, 10
# the benchmark's comparison of a returned distance with float64
DIST_TOL = 3e-5


@pytest.fixture(scope="module")
def laion():
    vecs, attrs = make_dataset(
        dataclasses.replace(DATASET_PRESETS["laion"], n=N, d=D))
    return vecs, attrs, KHIIndex.build(vecs, attrs,
                                       KHIConfig(M=16, builder="device"))


def _pinned(preds):
    """The boxes with attribute 0 pinned to its tied value 1 (about half
    the rows hold it)."""
    out = []
    for pr in preds:
        lo, hi = pr.lo.copy(), pr.hi.copy()
        lo[0] = hi[0] = 1.0
        out.append(qr.Predicate(lo, hi))
    return out


@pytest.fixture(scope="module", params=["wide", "pinned", "narrow"])
def boxes(request, laion):
    """Queries and boxes of one kind, 4 batches of B: the benchmark's
    graph-wide selectivity (12.5-37.5%), the same boxes pinned to the
    tied value, and narrow ones (0.8-2.3%, which the planner scans)."""
    vecs, attrs, _ = laion
    sigma = 1 / 64 if request.param == "narrow" else 1 / 4
    Q, preds = make_queries(vecs, attrs, n_queries=4 * B, sigma=sigma,
                            seed=5)
    if request.param == "pinned":
        preds = _pinned(preds)
    lo = np.stack([pr.lo for pr in preds]).astype(np.float32)
    hi = np.stack([pr.hi for pr in preds]).astype(np.float32)
    return request.param, Q, preds, lo, hi


def _service(index):
    params = eng.SearchParams(k=K, ef=128, c_e=10, c_n=32, expand_width=4,
                              strategy="auto", scan_threshold=N // 10)
    return KHIService(index, params,
                      config=ServeConfig(buckets=(B,), cache_size=0))


def test_fat_leaves_blacklist_every_dim(laion):
    """Ties leave nodes that no dim can split within the balance rule:
    the tree still validates and keeps Lemma 1's height, and every leaf
    holding more than ``leaf_capacity`` rows has every dim
    blacklisted."""
    _, attrs, index = laion
    t = index.tree
    t.validate()
    assert t.height - 1 <= t.height_bound()
    leaf = t.left < 0
    fat = leaf & (t.count > t.leaf_capacity)
    assert fat.any()
    assert (t.bl[fat] == (1 << t.m) - 1).all()
    # the ties themselves: attribute 0's most common value is 1
    vals, cnt = np.unique(attrs[:, 0], return_counts=True)
    assert vals[np.argmax(cnt)] == 1.0 and cnt.max() > 0.4 * N


def test_level_router_matches_twins_on_ties(laion, boxes):
    """The level router's entries and cardinality bound equal the numpy
    twins' (``range_filter``, ``estimate_cardinality``) and the DFS
    router's on the tied boxes, and its counts are the twin's live
    frontier."""
    _, _, index = laion
    _, _, preds, lo, hi = boxes
    di = eng.device_put_index(index)
    p = eng.derive_search_params(
        eng.SearchParams(k=K, ef=128, c_e=10, c_n=32), di)
    run = jax.jit(jax.vmap(lambda a, b: (rt.route_level_sync(di, a, b, p),
                                         rt.route_dfs(di, a, b, p)[0])))
    (ents, card, counts), e_dfs = jax.tree.map(
        np.asarray, run(jnp.asarray(lo), jnp.asarray(hi)))
    np.testing.assert_array_equal(ents, e_dfs)
    for i, pr in enumerate(preds):
        assert [int(x) for x in ents[i] if x >= 0] == \
            qr.range_filter(index, pr, p.c_e)
        assert card[i] == qr.estimate_cardinality(index, pr)
        w = qr.frontier_widths(index, pr, p.frontier_cap)
        np.testing.assert_array_equal(counts.live[i, :len(w)], w)
        assert not counts.live[i, len(w):].any()
    assert (counts.scan_steps.sum(axis=1) > 0).all()


def test_service_answers_on_ties(laion, boxes):
    """KHIService (the planner's dispatch, the graph program and the
    exact scan) against ``query_ref.brute_force``: every id in its box
    and none twice, full answers, distances that float64 confirms,
    scanned lanes exact, and graph recall@10 at the floor below."""
    vecs, attrs, index = laion
    kind, Q, preds, lo, hi = boxes
    svc = _service(index)
    v64 = vecs.astype(np.float64)
    recall, routes = [], []
    for s in range(0, len(preds), B):
        ids, dists = svc.search(Q[s:s + B], lo[s:s + B], hi[s:s + B])
        routes += list(svc.last_routes)
        for j in range(B):
            pr, q = preds[s + j], Q[s + j].astype(np.float64)
            got = ids[j][ids[j] >= 0]
            assert len(set(got.tolist())) == len(got)
            assert pr.matches(attrs[got]).all()
            assert len(got) == min(K, int(pr.matches(attrs).sum()))
            d64 = ((v64[got] - q) ** 2).sum(axis=1)
            err = np.abs(dists[j][:len(got)] - d64) / np.maximum(d64, 1)
            assert err.max(initial=0) <= DIST_TOL
            gt = qr.brute_force(vecs, attrs, Q[s + j], pr, K)
            if routes[-B + j] == "scan":
                gt_d = ((v64[gt] - q) ** 2).sum(axis=1)
                np.testing.assert_allclose(d64, gt_d, rtol=1e-5)
            recall.append(len(set(gt.tolist()) & set(got.tolist()))
                          / max(len(gt), 1))
    routes, recall = np.asarray(routes), np.asarray(recall)
    graph = routes == "graph"
    assert set(routes) <= {"graph", "scan"}
    assert kind == "narrow" or graph.all()
    # floor: the CPU run reads mean recall 1.0 (wide), 0.991 (pinned);
    # 0.9 leaves room for another build's graphs and catches a hop loop
    # that loses the in-box neighbours it filters
    if graph.any():
        assert recall[graph].mean() >= 0.9


def _replay_live(est, lo, hi):
    """The replay of one graph program run's ``router_live`` from
    ``HostCardEstimator``'s reachability: per tree level the largest
    live frontier over the run's lanes, summed over levels; and the
    number of levels any of them reaches."""
    reached = est.reach(lo, hi)[1]
    w = np.asarray([reached[:, nl].sum(axis=1).max() for nl in est.levels])
    return int(w.sum()), int((w > 0).sum())


def test_counters_on_ties(laion, boxes):
    """``router_live`` equals the host replay batch for batch; with a
    chunk at least ``frontier_cap`` wide ``router_chunks`` is one a
    live level; the hop loop's in-box neighbours are some of its fresh
    ones; and a batch the planner scans whole adds nothing."""
    _, _, index = laion
    kind, Q, preds, lo, hi = boxes
    svc = _service(index)
    assert svc.params.frontier_cap <= rt._CHUNK
    est = eng.Planner(index, svc.params)._estimators[0]
    names = eng.GRAPH_COUNTERS
    scan_only = 0
    for s in range(0, len(preds), B):
        before = {k: svc.stats[k] for k in names}
        svc.search(Q[s:s + B], lo[s:s + B], hi[s:s + B])
        got = {k: svc.stats[k] - before[k] for k in names}
        graph = np.nonzero(svc.last_routes == "graph")[0]
        if not graph.size:
            assert not any(got.values())
            scan_only += 1
            continue
        live, levels = _replay_live(est, lo[s:s + B][graph],
                                    hi[s:s + B][graph])
        assert got["router_live"] == live
        assert got["router_chunks"] == levels
        assert got["router_scan_steps"] > 0
        assert 0 < got["graph_inbox"] <= got["graph_fresh"]
    assert bool(scan_only) == (kind == "narrow")
