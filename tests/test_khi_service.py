"""Serving-layer behavior: bucket padding, LRU cache, stream chunking,
shard fan-out equality (DESIGN.md §3)."""

import numpy as np
import pytest

from repro.core.engine import SearchParams, search_batch
from repro.core.khi import KHIConfig
from repro.core.sharded import build_sharded, search_sharded_emulated
from repro.data import make_queries
from repro.serve import KHIService, Request, ServeConfig

PARAMS = SearchParams(k=10, ef=32, c_n=16)


@pytest.fixture(scope="module")
def workload(tiny_data):
    vecs, attrs = tiny_data
    Q, preds = make_queries(vecs, attrs, n_queries=21, sigma=1 / 16, seed=3)
    lo = np.stack([p.lo for p in preds]).astype(np.float32)
    hi = np.stack([p.hi for p in preds]).astype(np.float32)
    return Q, preds, lo, hi


@pytest.fixture(scope="module")
def service(tiny_index):
    return KHIService(tiny_index, PARAMS,
                      config=ServeConfig(buckets=(8, 16), cache_size=64))


def test_bucket_padding_matches_direct_engine(service, tiny_index, workload):
    """An odd-sized batch is padded to its bucket; results must equal the
    unpadded direct engine answer lane-for-lane."""
    Q, preds, lo, hi = workload
    ids, dists = service.search(Q[:5], lo[:5], hi[:5])
    want_ids, want_d, _ = search_batch(tiny_index, Q[:5], preds[:5], PARAMS)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(dists, want_d, rtol=1e-5)
    snap = service.snapshot()
    assert snap["traced_buckets"] == [8]       # 5 -> bucket 8
    assert snap["pad_lanes"] == 3


def test_cache_hit_identical_and_no_device_work(service, workload):
    Q, _, lo, hi = workload
    ids1, d1 = service.search(Q[:5], lo[:5], hi[:5])
    before = service.snapshot()
    ids2, d2 = service.search(Q[:5], lo[:5], hi[:5])
    after = service.snapshot()
    np.testing.assert_array_equal(ids1, ids2)
    np.testing.assert_array_equal(d1, d2)      # byte-identical, not allclose
    assert after["cache_hits"] - before["cache_hits"] == 5
    assert after["batches"] == before["batches"], "hit must skip the device"


def test_lru_eviction_order(service):
    """Direct cache poke: size bound holds and least-recently-used leaves
    first (no device work involved)."""
    svc = KHIService(service.index, PARAMS,
                     config=ServeConfig(buckets=(8,), cache_size=2))
    ids = np.arange(10, dtype=np.int32)
    d = np.zeros(10, np.float32)
    svc._cache_put(b"a", ids, d)
    svc._cache_put(b"b", ids + 1, d)
    assert svc._cache_get(b"a") is not None    # refresh 'a'; 'b' is LRU now
    svc._cache_put(b"c", ids + 2, d)           # evicts 'b'
    assert svc._cache_get(b"b") is None
    assert svc._cache_get(b"a") is not None
    assert svc._cache_get(b"c") is not None
    assert len(svc._cache) == 2


def test_stream_chunks_and_preserves_order(service, workload):
    """21 requests through max_batch=16 -> two device batches, in order."""
    Q, preds, lo, hi = workload
    fresh = KHIService(service.index, PARAMS,
                       config=ServeConfig(buckets=(8, 16), cache_size=0))
    res = list(fresh.serve_stream(
        Request(Q[i], lo[i], hi[i]) for i in range(21)))
    assert len(res) == 21
    ids, dists = service.search(Q, lo, hi)     # cache-backed oracle
    got = np.stack([r.ids for r in res])
    np.testing.assert_array_equal(got, ids)
    assert fresh.snapshot()["batches"] >= 2    # 16 + 5


def test_stream_empty_iterator(service):
    """An empty request stream yields nothing and touches no device."""
    before = service.snapshot()["batches"]
    assert list(service.serve_stream(iter([]))) == []
    assert service.snapshot()["batches"] == before


def test_stream_interleaved_hits_across_bucket_boundary(tiny_index,
                                                        workload):
    """A stream alternating cache hits and misses, chunked across the
    bucket boundary, yields exactly one correctly-flagged result per
    request in submission order."""
    Q, preds, lo, hi = workload
    svc = KHIService(tiny_index, PARAMS,
                     config=ServeConfig(buckets=(4, 8), cache_size=64))
    svc.search(Q[0:10:2], lo[0:10:2], hi[0:10:2])   # prime evens
    res = list(svc.serve_stream(
        Request(Q[i], lo[i], hi[i]) for i in range(10)))  # 8 + 2 chunks
    assert len(res) == 10
    assert [r.cached for r in res] == [i % 2 == 0 for i in range(10)]
    want, _ = svc.search(Q[:10], lo[:10], hi[:10])  # all cached now
    np.testing.assert_array_equal(np.stack([r.ids for r in res]), want)


def test_stream_mid_stream_swap_index(tiny_index, workload):
    """swap_index mid-stream: every submitted request still yields
    exactly one in-order result; requests buffered at swap time are
    answered on the new epoch/params."""
    import dataclasses

    Q, preds, lo, hi = workload
    svc = KHIService(tiny_index, PARAMS,
                     config=ServeConfig(buckets=(4,), cache_size=16))
    p2 = dataclasses.replace(PARAMS, ef=16)

    def gen():
        for i in range(6):
            yield Request(Q[i], lo[i], hi[i])
        svc.swap_index(tiny_index, params=p2)       # reqs 4,5 buffered
        for i in range(6, 12):
            yield Request(Q[i], lo[i], hi[i])

    res = list(svc.serve_stream(gen()))
    assert len(res) == 12
    got = np.stack([r.ids for r in res])
    want_old, _, _ = search_batch(tiny_index, Q[:4], preds[:4], PARAMS)
    want_new, _, _ = search_batch(tiny_index, Q[4:12], preds[4:12], p2)
    np.testing.assert_array_equal(got[:4], want_old)
    np.testing.assert_array_equal(got[4:], want_new)
    assert svc.snapshot()["epoch"] == 1


def test_submit_flush_tickets_and_cached_flag(service, workload):
    Q, _, lo, hi = workload
    q_fresh = (Q[20] + 0.25).astype(np.float32)   # never seen by the cache
    t_new = service.submit(Request(q_fresh, lo[20], hi[20]))
    t_old = service.submit(Request(Q[0], lo[0], hi[0]))  # cached earlier
    out = service.flush()
    assert set(out) == {t_new, t_old}
    assert out[t_old].cached and not out[t_new].cached
    ids, _ = service.search(q_fresh[None], lo[20:21], hi[20:21])
    np.testing.assert_array_equal(out[t_new].ids, ids[0])
    assert service.flush() == {}               # queue drained


def test_cache_disabled(service, workload):
    """cache_size=0: repeats hit the device every time."""
    Q, _, lo, hi = workload
    svc = KHIService(service.index, PARAMS,
                     config=ServeConfig(buckets=(8,), cache_size=0))
    svc.search(Q[:2], lo[:2], hi[:2])
    svc.search(Q[:2], lo[:2], hi[:2])
    snap = svc.snapshot()
    assert snap["cache_hits"] == 0 and snap["batches"] == 2
    assert snap["cache_entries"] == 0


def test_sharded_service_matches_emulated_fanout(tiny_data, workload):
    vecs, attrs = tiny_data
    Q, preds, lo, hi = workload
    skhi = build_sharded(vecs, attrs, 3, KHIConfig(M=16, builder="bulk"))
    svc = KHIService(skhi, PARAMS, config=ServeConfig(buckets=(8,),
                                                      cache_size=0))
    ids, dists = svc.search(Q[:8], lo[:8], hi[:8])
    mi, md, _ = search_sharded_emulated(skhi, Q[:8], lo[:8], hi[:8], PARAMS)
    np.testing.assert_array_equal(ids, np.asarray(mi))
    np.testing.assert_allclose(dists, np.asarray(md), rtol=1e-5)


def test_swap_index_epoch_invalidates_cache(tiny_data, tiny_index, workload):
    """Hot-swap: a rebuilt index replaces the live one, the epoch bumps and
    cached results from the old epoch can never be served again."""
    vecs, attrs = tiny_data
    Q, _, lo, hi = workload
    svc = KHIService(tiny_index, PARAMS,
                     config=ServeConfig(buckets=(8,), cache_size=64))
    ids_old, _ = svc.search(Q[:3], lo[:3], hi[:3])
    assert svc.snapshot()["cache_entries"] == 3

    rebuilt = build_sharded(vecs, attrs, 2, KHIConfig(M=16, builder="device"))
    svc.swap_index(rebuilt)
    snap = svc.snapshot()
    assert snap["epoch"] == 1 and snap["epoch_swaps"] == 1
    assert snap["cache_entries"] == 0
    before = svc.snapshot()["batches"]
    ids_new, dists_new = svc.search(Q[:3], lo[:3], hi[:3])
    assert svc.snapshot()["batches"] == before + 1, \
        "old-epoch cache entry served after swap"
    # new epoch answers come from the new (sharded, device-built) index
    mi, md, _ = search_sharded_emulated(
        rebuilt, Q[:3], lo[:3], hi[:3], svc.params)
    np.testing.assert_array_equal(ids_new, np.asarray(mi))
    np.testing.assert_allclose(dists_new, np.asarray(md), rtol=1e-5)


def test_swap_index_drains_pending_on_old_epoch(tiny_data, tiny_index,
                                                workload):
    """Queued requests are not dropped by a swap: they flush against the
    index they targeted and their Results come back from swap_index."""
    vecs, attrs = tiny_data
    Q, preds, lo, hi = workload
    svc = KHIService(tiny_index, PARAMS,
                     config=ServeConfig(buckets=(8,), cache_size=0))
    want, _ = svc.search(Q[:1], lo[:1], hi[:1])
    t = svc.submit(Request(Q[0], lo[0], hi[0]))
    rebuilt = build_sharded(vecs, attrs, 3, KHIConfig(M=16, builder="device"))
    drained = svc.swap_index(rebuilt)
    assert set(drained) == {t}
    np.testing.assert_array_equal(drained[t].ids, want[0])
    assert svc.flush() == {}                   # nothing left behind
    assert svc.epoch == 1


def test_swap_index_no_drain_runs_on_new_epoch(tiny_data, tiny_index,
                                               workload):
    vecs, attrs = tiny_data
    Q, _, lo, hi = workload
    svc = KHIService(tiny_index, PARAMS,
                     config=ServeConfig(buckets=(8,), cache_size=0))
    t = svc.submit(Request(Q[0], lo[0], hi[0]))
    rebuilt = build_sharded(vecs, attrs, 2, KHIConfig(M=16, builder="device"))
    assert svc.swap_index(rebuilt, drain=False) == {}
    out = svc.flush()                          # executes on the new epoch
    mi, _, _ = search_sharded_emulated(
        rebuilt, Q[:1], lo[:1], hi[:1], svc.params)
    np.testing.assert_array_equal(out[t].ids, np.asarray(mi)[0])


def test_swap_index_no_drain_back_to_back(tiny_data, tiny_index, workload):
    """Two drain=False swaps before a flush: the queued request must run
    on the FINAL epoch's index (never the intermediate one), both swaps
    return empty drains, and the epoch/cache bookkeeping advances twice."""
    vecs, attrs = tiny_data
    Q, _, lo, hi = workload
    svc = KHIService(tiny_index, PARAMS,
                     config=ServeConfig(buckets=(8,), cache_size=64))
    t = svc.submit(Request(Q[0], lo[0], hi[0]))
    mid = build_sharded(vecs, attrs, 2, KHIConfig(M=16, builder="device"))
    final = build_sharded(vecs, attrs, 3, KHIConfig(M=16, builder="device"))
    assert svc.swap_index(mid, drain=False) == {}
    assert svc.swap_index(final, drain=False) == {}
    assert svc.epoch == 2 and svc.snapshot()["epoch_swaps"] == 2
    assert svc.snapshot()["cache_entries"] == 0
    out = svc.flush()
    mi, _, _ = search_sharded_emulated(final, Q[:1], lo[:1], hi[:1],
                                       svc.params)
    np.testing.assert_array_equal(out[t].ids, np.asarray(mi)[0])


def test_cache_keys_invalidate_across_back_to_back_swaps(tiny_data,
                                                         tiny_index,
                                                         workload):
    """Per-epoch cache keys: each swap makes prior entries unreachable
    (a fresh device batch runs), and re-asking within an epoch hits."""
    vecs, attrs = tiny_data
    Q, _, lo, hi = workload
    svc = KHIService(tiny_index, PARAMS,
                     config=ServeConfig(buckets=(8,), cache_size=64))
    indexes = [tiny_index,
               build_sharded(vecs, attrs, 2, KHIConfig(M=16,
                                                       builder="device")),
               build_sharded(vecs, attrs, 3, KHIConfig(M=16,
                                                       builder="device"))]
    for epoch, nxt in enumerate(indexes[1:], start=1):
        before = svc.snapshot()
        svc.search(Q[:3], lo[:3], hi[:3])          # miss: fresh epoch
        svc.search(Q[:3], lo[:3], hi[:3])          # hit: same epoch
        after = svc.snapshot()
        assert after["batches"] == before["batches"] + 1
        assert after["cache_hits"] == before["cache_hits"] + 3
        assert after["cache_entries"] == 3
        svc.swap_index(nxt)
        assert svc.snapshot()["cache_entries"] == 0
        assert svc.epoch == epoch


@pytest.mark.parametrize("strategy,programs", [
    ("graph", {"graph"}), ("auto", {"graph", "scan"})])
def test_compiled_hlo_names_each_program(tiny_index, strategy, programs):
    """compiled_hlo returns the compiled text of every whole-batch program
    that serves a bucket: one graph program, or the planner's graph and
    scan programs under strategy="auto"."""
    svc = KHIService(tiny_index, SearchParams(k=10, ef=32, c_n=16,
                                              strategy=strategy),
                     config=ServeConfig(buckets=(8,), cache_size=0))
    hlo = svc.compiled_hlo(8)
    assert set(hlo) == programs
    assert all(text.startswith("HloModule") for text in hlo.values())


def test_bad_bucket_config_rejected():
    with pytest.raises(ValueError, match="buckets"):
        ServeConfig(buckets=(32, 8))
    with pytest.raises(ValueError, match="buckets"):
        ServeConfig(buckets=())
    # non-positive sizes: a 0/negative bucket would trace a degenerate
    # batch shape (and max_batch could go <= 0)
    with pytest.raises(ValueError, match="positive"):
        ServeConfig(buckets=(0, 8))
    with pytest.raises(ValueError, match="positive"):
        ServeConfig(buckets=(-4, 8))
    with pytest.raises(ValueError, match="cache_size"):
        ServeConfig(buckets=(8,), cache_size=-1)


def test_bad_on_undersized_rejected_at_construction(tiny_index):
    """An invalid on_undersized must fail when the service is built, not
    at the first undersized-params validation deep in a request."""
    with pytest.raises(ValueError, match="on_undersized"):
        KHIService(tiny_index, PARAMS, on_undersized="explode")


def test_khi_serve_config_helpers():
    """configs.khi_serve helpers stay in sync with the real dataclasses."""
    from repro.configs.khi_serve import config, smoke_config

    for cfg in (config(), smoke_config()):
        p = cfg.search_params()
        assert (p.k, p.ef, p.c_e, p.c_n) == (cfg.k, cfg.ef, cfg.c_e, cfg.c_n)
        assert p.backend == cfg.backend
        assert (p.strategy, p.scan_threshold) == (cfg.strategy,
                                                  cfg.scan_threshold)
        assert p.strategy == "auto"    # the §10 serving default
        sc = cfg.serve_config()
        assert sc.buckets == cfg.buckets
        assert sc.cache_size == cfg.cache_size
        assert sc.max_batch == max(cfg.buckets)
