"""Collective query pipeline on an emulated multi-device mesh (DESIGN.md §14).

Two halves:

* host-side pins that run in the tier-1 suite on one device — the halving
  merge simulated round-by-round against ``_merge_topk``, the device dedup
  against the numpy reference, pad-waste accounting, dry-run specs with
  quant replicas, and ``route_level_windows`` against the host Planner;
* real-mesh tests that need 8 emulated devices (CI runs this file again
  under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; on fewer
  devices they skip) — bit-identity of ``make_sharded_search_fn`` against
  ``search_sharded_emulated`` on a 2x4 (data, model) mesh across strategy,
  quant and merge, mixed-strategy batches whose data groups take different
  dispatch branches, service-level mesh serving, and an
  ``elastic_reshard`` round-trip answered collectively.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import engine as eng
from repro.core.engine import Planner, SearchParams, _merge_dedup, \
    _merge_dedup_jnp, validate_search_params, with_quant_replica
from repro.core.khi import KHIConfig, KHIIndex
from repro.core.router import route_level_windows
from repro.core.sharded import (ShardedKHI, _merge_topk, _merge_topk_halving,
                                _pair_merge_k, _resolve_merge, build_sharded,
                                make_sharded_search_fn, merge_bytes_per_device,
                                search_sharded_emulated, sharded_input_specs,
                                stack_shards)
from repro.core.util import pow2_at_least
from repro.data import DatasetSpec, make_dataset, make_queries
from repro.distributed.elastic import elastic_reshard
from repro.launch.mesh import make_query_mesh

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


# ---------------------------------------------------------------------------
# host-side pins (tier-1, single device)
# ---------------------------------------------------------------------------

def _host_halving(gids, dists, k):
    """Simulate the halving rounds with _pair_merge_k on a (S, B, k) stack:
    shard s's buffers evolve exactly as device s's do under ppermute."""
    S = gids.shape[0]
    tie = (np.arange(S)[:, None, None] * k
           + np.arange(k)[None, None, :]).astype(np.int32)
    tie = np.broadcast_to(tie, gids.shape).copy()
    ids, d, t = gids.copy(), dists.copy(), tie
    for rnd in range(S.bit_length() - 1):
        bit = 1 << rnd
        perm = np.arange(S) ^ bit
        oi, od, ot = ids[perm], d[perm], t[perm]
        out = [np.asarray(x) for x in zip(*[
            _pair_merge_k(jnp.asarray(ids[s]), jnp.asarray(d[s]),
                          jnp.asarray(t[s]), jnp.asarray(oi[s]),
                          jnp.asarray(od[s]), jnp.asarray(ot[s]), k)
            for s in range(S)])]
        ids, d, t = np.stack(out[0]), np.stack(out[1]), np.stack(out[2])
    return ids, d


@pytest.mark.parametrize("S", [2, 4, 8])
def test_halving_simulation_matches_merge_topk(S):
    rng = np.random.default_rng(S)
    B, k = 5, 10
    # sorted per-shard top-k lists with deliberate cross-shard distance
    # ties and invalid (-1, inf) tails
    dists = np.sort(rng.integers(0, 6, (S, B, k)).astype(np.float32), axis=-1)
    gids = rng.integers(0, 10_000, (S, B, k)).astype(np.int32)
    dists[:, :, -2:] = np.inf
    gids[:, :, -2:] = -1
    ei, ed = _merge_topk(jnp.asarray(gids), jnp.asarray(dists), k)
    hi_, hd = _host_halving(gids, dists, k)
    # every simulated device must finish with the identical replicated
    # answer, in _merge_topk's exact order (ids included: tie-break pin)
    for s in range(S):
        np.testing.assert_array_equal(hi_[s], np.asarray(ei))
        np.testing.assert_array_equal(hd[s], np.asarray(ed))


def test_merge_dedup_jnp_matches_host():
    rng = np.random.default_rng(0)
    B, k = 6, 8
    ids_a = rng.integers(-1, 40, (B, k)).astype(np.int32)
    ids_b = rng.integers(-1, 40, (B, k)).astype(np.int32)
    d_a = np.where(ids_a < 0, np.inf,
                   rng.integers(0, 5, (B, k))).astype(np.float32)
    d_b = np.where(ids_b < 0, np.inf,
                   rng.integers(0, 5, (B, k))).astype(np.float32)
    # both inputs sorted, as the merge contract requires
    oa = np.lexsort((ids_a, d_a), axis=-1)
    ob = np.lexsort((ids_b, d_b), axis=-1)
    ids_a, d_a = (np.take_along_axis(x, oa, 1) for x in (ids_a, d_a))
    ids_b, d_b = (np.take_along_axis(x, ob, 1) for x in (ids_b, d_b))
    hi_, hd = _merge_dedup(ids_a, d_a, ids_b, d_b, k)
    ji, jd = _merge_dedup_jnp(jnp.asarray(ids_a), jnp.asarray(d_a),
                              jnp.asarray(ids_b), jnp.asarray(d_b), k)
    np.testing.assert_array_equal(np.asarray(ji), hi_)
    np.testing.assert_array_equal(np.asarray(jd), hd)


def test_merge_bytes_and_resolution():
    # halving wins from S = 4 up; S = 1 needs no merge traffic at all
    assert merge_bytes_per_device(10, 1, "halving") == 0
    assert merge_bytes_per_device(10, 4, "halving") == 12 * 10 * 2
    assert merge_bytes_per_device(10, 4, "allgather") == 8 * 10 * 3
    # tie at S = 4 (12k·log2 vs 8k·(S-1)); halving strictly wins beyond
    assert (merge_bytes_per_device(10, 4, "halving")
            <= merge_bytes_per_device(10, 4, "allgather"))
    for S in (8, 16, 64):
        assert (merge_bytes_per_device(10, S, "halving")
                < merge_bytes_per_device(10, S, "allgather"))
    assert _resolve_merge("auto", 4) == "halving"
    assert _resolve_merge("auto", 3) == "allgather"
    assert _resolve_merge("auto", 1) == "allgather"
    with pytest.raises(ValueError, match="power-of-two"):
        _resolve_merge("halving", 3)
    with pytest.raises(ValueError, match="halving"):
        _resolve_merge("bogus", 4)


def test_pad_waste_round_robin_balance(tiny_data):
    vecs, attrs = tiny_data
    S = 4
    skhi = build_sharded(vecs, attrs, S, KHIConfig(M=16, builder="bulk"))
    assert len(skhi.pad_waste) == 3
    # round-robin shard sizes differ by at most 1 object, so padded rows
    # are a vanishing fraction; node/level counts track size closely
    row_waste, node_waste, level_waste = skhi.pad_waste
    eps = 0.02
    assert row_waste < 1 / S + eps
    assert node_waste < 1 / S + eps
    assert level_waste < 1 / S + eps
    # pad_waste is static pytree aux: it must survive jit boundaries and
    # not become a traced leaf
    out = jax.jit(lambda s: s.di.count.sum())(skhi)
    assert int(out) > 0
    leaves, treedef = jax.tree.flatten(skhi)
    rebuilt = jax.tree.unflatten(treedef, leaves)
    assert rebuilt.pad_waste == skhi.pad_waste


def test_sharded_input_specs_quant_planes():
    kw = dict(n_per_shard=64, d=16, m=2, height=3, nodes_per_shard=31,
              M=8, n_shards=4, batch=8)
    skhi, _ = sharded_input_specs(**kw)
    assert skhi.di.qvecs is None and skhi.di.qscale is None
    skhi, _ = sharded_input_specs(quant="bf16", **kw)
    assert skhi.di.qvecs.shape == (4, 64, 16)
    assert skhi.di.qvecs.dtype == jnp.bfloat16
    assert skhi.di.qscale is None
    skhi, _ = sharded_input_specs(quant="int8", **kw)
    assert skhi.di.qvecs.dtype == jnp.int8
    assert skhi.di.qscale.shape == (4, 64, 1)
    assert skhi.di.qscale.dtype == jnp.float32
    with pytest.raises(ValueError, match="quant"):
        sharded_input_specs(quant="fp4", **kw)


def test_quantized_collective_lowers_from_specs():
    # dry-run contract: a quantized scan program lowers against
    # ShapeDtypeStructs alone (no index build, no skhi validation)
    mesh = make_query_mesh(1, 1)
    skhi_sds, qs = sharded_input_specs(
        n_per_shard=64, d=16, m=2, height=3, nodes_per_shard=31, M=8,
        n_shards=1, batch=8, quant="int8")
    fn = make_sharded_search_fn(
        SearchParams(k=4, strategy="scan", quant="int8"), mesh)
    lowered = fn.lower(skhi_sds, qs["queries"], qs["qlo"], qs["qhi"])
    assert lowered.compile() is not None


def test_collective_auto_requires_threshold_source():
    mesh = make_query_mesh(1, 1)
    with pytest.raises(ValueError, match="skhi"):
        make_sharded_search_fn(SearchParams(strategy="auto"), mesh)
    with pytest.raises(ValueError, match="skhi"):
        make_sharded_search_fn(SearchParams(strategy="hybrid"), mesh)
    # auto with an explicit threshold needs no index
    fn = make_sharded_search_fn(
        SearchParams(strategy="auto", scan_threshold=32), mesh)
    assert callable(fn)


def test_route_level_windows_matches_host_planner(tiny_data, tiny_index,
                                                  tiny_queries):
    vecs, attrs = tiny_data
    _, preds = tiny_queries
    qlo = np.stack([pr.lo for pr in preds]).astype(np.float32)
    qhi = np.stack([pr.hi for pr in preds]).astype(np.float32)
    skhi = stack_shards([tiny_index])
    thr = 64
    p = validate_search_params(
        SearchParams(k=8, strategy="hybrid", node_scan_threshold=thr),
        skhi.di, on_undersized="adjust")
    planner = Planner(skhi, p)
    plan = planner.plan(qlo, qhi)
    di = jax.tree.map(lambda x: x[0], skhi.di)
    W = pow2_at_least(int(di.start.shape[0]))
    card, n_small, n_large, wstarts, wcounts = jax.vmap(
        lambda lo, hi: route_level_windows(di, jnp.asarray(lo),
                                           jnp.asarray(hi), p,
                                           node_thr=thr, W=W)
    )(jnp.asarray(qlo), jnp.asarray(qhi))
    np.testing.assert_array_equal(np.asarray(n_small), plan.n_windows)
    anti = planner._estimators[0].antichain(qlo, qhi)   # (B, P) bool
    cnt = planner._node_count[0]
    exp_large = (anti & (cnt > thr)[None, :]).sum(axis=1)
    np.testing.assert_array_equal(np.asarray(n_large), exp_large)
    start = np.asarray(di.start)
    count = np.asarray(di.count)
    small_nodes = plan.small_nodes[0]                  # (B, P) bool
    for b in range(qlo.shape[0]):
        nodes = np.nonzero(small_nodes[b])[0]
        exp = sorted((int(start[n]), int(count[n])) for n in nodes)
        got_s = np.asarray(wstarts[b])
        got_c = np.asarray(wcounts[b])
        got = [(int(s), int(c)) for s, c in zip(got_s, got_c) if s >= 0]
        assert got == exp, f"query {b}: windows {got} != host {exp}"


# ---------------------------------------------------------------------------
# real-mesh tests (8 emulated devices; CI step re-runs this file with
# XLA_FLAGS=--xla_force_host_platform_device_count=8)
# ---------------------------------------------------------------------------

_P2 = DatasetSpec("p2", n=640, d=16, m=2, seed=0)


@pytest.fixture(scope="module")
def mesh_bundle():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    vecs, attrs = make_dataset(_P2)
    skhi = build_sharded(vecs, attrs, 4, KHIConfig(M=16, builder="bulk"))
    Q, preds = make_queries(vecs, attrs, n_queries=16, sigma=1 / 4, seed=3)
    qlo = np.stack([pr.lo for pr in preds]).astype(np.float32)
    qhi = np.stack([pr.hi for pr in preds]).astype(np.float32)
    # widen some boxes (graph lanes) and shrink others (scan lanes) so
    # auto/hybrid dispatch genuinely branches within the batch
    qlo[:6] = attrs.min(0) - 1
    qhi[:6] = attrs.max(0) + 1
    mesh = make_query_mesh(4, 2)
    return vecs, attrs, skhi, mesh, Q, qlo, qhi


@needs_mesh
@pytest.mark.parametrize("strategy,quant", [
    ("graph", "none"), ("scan", "none"), ("scan", "int8"),
    ("auto", "none"), ("auto", "int8"), ("hybrid", "none"),
])
@pytest.mark.parametrize("merge", ["halving", "allgather"])
def test_collective_bitidentical_to_emulated(mesh_bundle, strategy, quant,
                                             merge):
    _, _, skhi, mesh, Q, qlo, qhi = mesh_bundle
    p = SearchParams(k=10, ef=48, c_n=16, strategy=strategy, quant=quant)
    sk = skhi
    if quant != "none":
        sk = dataclasses.replace(skhi, di=with_quant_replica(skhi.di, quant))
    ei, ed, _ = search_sharded_emulated(sk, Q, qlo, qhi, p)
    fn = make_sharded_search_fn(p, mesh, skhi=sk, on_undersized="adjust",
                                merge=merge)
    ci, cd = jax.device_get(fn(sk, Q, qlo, qhi))
    np.testing.assert_array_equal(ci, np.asarray(ei))
    np.testing.assert_array_equal(cd, np.asarray(ed))


@needs_mesh
def test_mixed_strategy_batch_across_data_groups(mesh_bundle):
    """The two data groups take DIFFERENT dispatch branches: group 0's
    lanes are all wide boxes (graph), group 1's all narrow (scan). This is
    the shape that deadlocks if any collective sits inside a dispatch
    lax.cond — the regression pin for §14's collectives-outside-conds
    rule."""
    _, attrs, skhi, mesh, Q, qlo, qhi = mesh_bundle
    B = Q.shape[0]
    qlo2, qhi2 = qlo.copy(), qhi.copy()
    qlo2[:B // 2] = attrs.min(0) - 1        # data group 0: pure graph
    qhi2[:B // 2] = attrs.max(0) + 1
    center = attrs[0]
    qlo2[B // 2:] = center - 1e-3           # data group 1: tiny boxes
    qhi2[B // 2:] = center + 1e-3
    p = SearchParams(k=10, ef=48, c_n=16, strategy="auto")
    ei, ed, _ = search_sharded_emulated(skhi, Q, qlo2, qhi2, p)
    fn = make_sharded_search_fn(p, mesh, skhi=skhi, on_undersized="adjust")
    ci, cd = jax.device_get(fn(skhi, Q, qlo2, qhi2))
    np.testing.assert_array_equal(ci, np.asarray(ei))
    np.testing.assert_array_equal(cd, np.asarray(ed))


@needs_mesh
def test_halving_merge_collective_unit():
    rng = np.random.default_rng(1)
    S, B, k = 8, 4, 6
    mesh = make_query_mesh(S, 1)
    dists = np.sort(rng.integers(0, 4, (S, B, k)).astype(np.float32), axis=-1)
    gids = rng.integers(0, 999, (S, B, k)).astype(np.int32)
    ref_i, ref_d = _merge_topk(jnp.asarray(gids), jnp.asarray(dists), k)

    from jax.sharding import PartitionSpec as P

    def body(g, d):
        return _merge_topk_halving(g[0], d[0], k, "model", S)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P("model"), P("model")),
                       out_specs=(P(None), P(None)), check_vma=False)
    ci, cd = jax.jit(fn)(jnp.asarray(gids), jnp.asarray(dists))
    np.testing.assert_array_equal(np.asarray(ci), np.asarray(ref_i))
    np.testing.assert_array_equal(np.asarray(cd), np.asarray(ref_d))


@needs_mesh
def test_service_collective_mesh_serving(mesh_bundle):
    from repro.serve.khi_service import KHIService
    _, _, skhi, mesh, Q, qlo, qhi = mesh_bundle
    p = SearchParams(k=10, ef=48, c_n=16, strategy="auto")
    svc = KHIService(skhi, p, mesh=mesh)
    ids, dists = svc.search(Q, qlo, qhi)
    ei, ed, _ = search_sharded_emulated(skhi, Q, qlo, qhi, p)
    np.testing.assert_array_equal(ids, np.asarray(ei))
    np.testing.assert_array_equal(dists, np.asarray(ed))


@needs_mesh
def test_elastic_reshard_collective_roundtrip(mesh_bundle):
    """Lose a shard, rebuild it with elastic_reshard, re-stack, and answer
    on the mesh: the partition is unchanged so the collective answers must
    be bit-identical to the pre-loss index (satellite: elastic round-trip
    on an actual mesh)."""
    vecs, attrs, skhi, mesh, Q, qlo, qhi = mesh_bundle
    p = SearchParams(k=10, ef=48, c_n=16, strategy="graph")
    fn = make_sharded_search_fn(p, mesh, skhi=skhi, on_undersized="adjust")
    ref_i, ref_d = jax.device_get(fn(skhi, Q, qlo, qhi))

    cfg = KHIConfig(M=16, builder="bulk")
    shard_of = np.arange(len(vecs)) % 4
    survivors = {
        s: KHIIndex.build(vecs[shard_of == s], attrs[shard_of == s], cfg)
        for s in range(4) if s != 2       # shard 2's host is lost
    }
    rebuilt = elastic_reshard(vecs, attrs, survivors, 4, 4, cfg)
    assert set(rebuilt) == {0, 1, 2, 3}
    skhi2 = stack_shards([rebuilt[s] for s in range(4)])
    got_i, got_d = jax.device_get(fn(skhi2, Q, qlo, qhi))
    np.testing.assert_array_equal(got_i, ref_i)
    np.testing.assert_array_equal(got_d, ref_d)
