"""The serving path's own tracing (DESIGN.md §3 "Observability"): the
``khi.*`` host spans in a profiler trace, the graph program's
``khi_router`` / ``khi_hop_loop`` scopes, the hop-loop counters in
``KHIService.stats`` and the per-lane dispatch record."""

import glob
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import engine as eng
from repro.core import query_ref as qr
from repro.core import router as rt
from repro.serve import KHIService, ServeConfig

BASE = dict(k=10, ef=32, c_n=16)
B = 8


def _boxes(preds):
    return (np.stack([p.lo for p in preds]).astype(np.float32),
            np.stack([p.hi for p in preds]).astype(np.float32))


@pytest.fixture(scope="module")
def batch(tiny_index, tiny_queries):
    """B queries whose boxes the auto planner splits between the scan and
    the graph program, and the threshold that splits them."""
    Q, preds = tiny_queries
    Q, (lo, hi) = Q[:B], _boxes(preds[:B])
    card = eng.Planner(tiny_index, eng.SearchParams(strategy="auto", **BASE)
                       ).plan(lo, hi).card
    thresh = int(np.median(card))
    assert 0 < (card <= thresh).sum() < B
    return Q, lo, hi, thresh


def _service(tiny_index, thresh, strategy="auto", cache_size=64):
    params = eng.SearchParams(strategy=strategy, scan_threshold=thresh,
                              **BASE)
    return KHIService(tiny_index, params,
                      config=ServeConfig(buckets=(B,), cache_size=cache_size))


def _khi_spans(trace_dir):
    """[(name, start_ns, end_ns, args)] of the khi.* host spans."""
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)) for e in line.events
                    if e.name.startswith("khi.")]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def test_spans_nest_inside_search_in_order(tiny_index, batch, tmp_path):
    """One mixed batch with one cache hit: every khi.* span lies inside
    khi.search, in the order the work runs, with one dispatch / fetch
    pair per program run."""
    Q, lo, hi, thresh = batch
    svc = _service(tiny_index, thresh)
    svc.search(Q[:1], lo[:1], hi[:1])             # lane 0 becomes a hit
    with jax.profiler.trace(str(tmp_path)):
        svc.search(Q, lo, hi)
    assert list(svc.last_routes[:1]) == ["cache"]
    spans = _khi_spans(tmp_path)
    (search,) = [s for s in spans if s[0] == "khi.search"]
    assert search[3] == {"seq": 2, "lanes": B, "tier": 0}
    inner = [s for s in spans if s is not search]
    assert all(search[1] <= s[1] and s[2] <= search[2] for s in inner)
    names = [s[0] if "program" not in s[3] else
             f"{s[0]}:{s[3]['program']}" for s in inner]
    assert names == ["khi.cache", "khi.plan",
                     "khi.dispatch:graph", "khi.fetch:graph",
                     "khi.dispatch:scan", "khi.fetch:scan",
                     "khi.merge", "khi.cache"]
    # siblings: no span starts before the one ahead of it has ended
    assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))


def test_graph_program_carries_phase_scopes(tiny_index, batch):
    """The compiled graph program names its phases in its op_name
    metadata (jax.named_scope; under vmap as ``vmap(khi_router)``)."""
    svc = _service(tiny_index, batch[3], cache_size=0)
    names = re.findall(r'op_name="([^"]*)"', svc.compiled_hlo(B)["graph"])
    router = [n for n in names if "khi_router" in n]
    loop = [n for n in names if "khi_hop_loop" in n]
    assert router and loop
    assert not any("khi_hop_loop" in n for n in router)
    assert any("/while/body" in n for n in loop)


@pytest.mark.parametrize("strategy", ["auto", "graph"])
def test_counters_match_planner_hops_and_plan(tiny_index, batch, strategy):
    """graph_lanes / graph_hops / graph_lane_steps equal what the
    Planner's hops and Plan give for the same batch; bucket pads, scan
    lanes and cache hits add no hops."""
    Q, lo, hi, thresh = batch
    svc = _service(tiny_index, thresh, strategy)
    planner = eng.Planner(tiny_index, svc.params)
    _, _, hops, plan = planner.search(Q, lo, hi)
    graph = ~plan.use_scan
    (run_lanes, run_hops), = plan.graph_runs
    assert run_lanes == (B if graph.all() else
                         1 << (int(graph.sum()) - 1).bit_length())
    assert run_hops == hops[graph].max()
    svc.search(Q, lo, hi)
    s = svc.snapshot()
    assert s["graph_lanes"] == graph.sum()
    assert s["graph_hops"] == hops.sum() == hops[graph].sum()
    assert s["graph_lane_steps"] == run_lanes * run_hops
    assert list(svc.last_routes) == np.where(graph, "graph", "scan").tolist()
    np.testing.assert_array_equal(svc.last_hops, hops)

    svc.search(Q, lo, hi)                          # every lane a hit
    assert svc.snapshot()["graph_hops"] == s["graph_hops"]
    assert set(svc.last_routes) == {"cache"} and not svc.last_hops.any()
    svc.search(Q[:3] + np.float32(1e-3), lo[:3], hi[:3])   # 5 bucket pads
    _, _, hops3, plan3 = planner.search(Q[:3] + np.float32(1e-3), lo[:3],
                                        hi[:3])
    t = svc.snapshot()
    assert t["pad_lanes"] == B - 3
    assert t["graph_lanes"] - s["graph_lanes"] == (~plan3.use_scan).sum()
    assert t["graph_hops"] - s["graph_hops"] == hops3.sum()


@pytest.mark.parametrize("strategy", ["auto", "graph"])
def test_router_chunks_counter(tiny_index, batch, strategy):
    """router_chunks sums the chunk count each graph program run returns:
    per level, the largest ceil(live / C) over the run's lanes, pads
    included. With C >= frontier_cap (the tiny index's frontier is
    narrower than one chunk) a level costs one chunk while any lane's
    frontier is live there, and none once every lane's is empty, so a
    run costs its deepest lane's level count, at most H. Cache hits add
    nothing, nor do scan lanes: a lone scan lane's bucket pads (empty
    boxes, card 0) take the graph program, whose root level alone is
    live, one chunk."""
    Q, lo, hi, thresh = batch
    svc = _service(tiny_index, thresh, strategy)
    assert svc.params.frontier_cap <= rt._CHUNK
    _, _, _, plan = eng.Planner(tiny_index, svc.params).search(Q, lo, hi)
    graph = np.nonzero(~plan.use_scan)[0]
    levels = max(len(qr.frontier_widths(tiny_index, qr.Predicate(lo[i],
                                                                 hi[i])))
                 for i in graph)
    H = eng.device_put_index(tiny_index).nbrs.shape[1]
    assert plan.router_chunks == levels <= H * len(plan.graph_runs)
    svc.search(Q, lo, hi)
    s = svc.snapshot()["router_chunks"]
    assert s == plan.router_chunks
    svc.search(Q, lo, hi)                          # every lane a hit
    assert svc.snapshot()["router_chunks"] == s
    if strategy == "auto":
        scan = np.nonzero(plan.use_scan)[0][:1]    # a scan-only batch
        svc.search(Q[scan] + np.float32(1e-3), lo[scan], hi[scan])
        assert list(svc.last_routes) == ["scan"]
        assert svc.snapshot()["router_chunks"] == s + 1


def test_answers_identical_with_profiler_on(tiny_index, batch, tmp_path):
    Q, lo, hi, thresh = batch
    off = _service(tiny_index, thresh, cache_size=0).search(Q, lo, hi)
    svc = _service(tiny_index, thresh, cache_size=0)
    with jax.profiler.trace(str(tmp_path)):
        on = svc.search(Q, lo, hi)
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)


def test_snapshot_reports_counters_not_device_qps(tiny_index, batch):
    snap = _service(tiny_index, batch[3]).snapshot()
    assert not {"device_qps", "device_seconds", "device_queries"} & set(snap)
    assert {"graph_lanes", "graph_hops", "graph_lane_steps",
            "router_chunks"} <= set(snap)
