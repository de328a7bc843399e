"""Quantized score path (DESIGN.md §12): replica construction, kernel vs
oracle parity for the int8 gather/scan variants, and the exact-f32-rerank
contract — the engine's quantized strategies must return ids bit-identical
to the f32 oracle whenever the true top-k survives the over-fetch, and the
targeted pins below construct cases where the quantized ORDER is provably
wrong at the k boundary so the rerank is what fixes it.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import engine as eng
from repro.core.khi import KHIConfig, KHIIndex
from repro.kernels import quant as kq
from repro.kernels.ref import (gather_l2_filter_q8_ref, scan_topk_q8_ref,
                               scan_topk_ref)

BACKENDS = ("jnp", "pallas_gather_l2_filter")


def _workload(B, N, D, M, seed):
    rng = np.random.default_rng(seed)
    corpus = rng.standard_normal((N, D)).astype(np.float32)
    attrs = rng.uniform(0, 10, (N, M)).astype(np.float32)
    q = rng.standard_normal((B, D)).astype(np.float32)
    qlo = rng.uniform(0, 6, (B, M)).astype(np.float32)
    qhi = qlo + rng.uniform(0, 5, (B, M)).astype(np.float32)
    return corpus, attrs, q, qlo, qhi


# ------------------------------------------------------------ replica

def test_quantize_rows_i8_properties():
    rng = np.random.default_rng(0)
    vecs = jnp.asarray(rng.standard_normal((32, 12)), jnp.float32)
    q, s = kq.quantize_rows_i8(vecs)
    assert q.dtype == jnp.int8 and s.shape == (32, 1)
    assert int(jnp.max(jnp.abs(q.astype(jnp.int32)))) <= 127
    # per-row max-abs scale: dequant error bounded by scale / 2 per lane
    deq = np.asarray(kq.dequant_rows(q, s))
    err = np.abs(deq - np.asarray(vecs))
    assert np.all(err <= np.asarray(s) / 2 + 1e-7)


def test_quantize_rows_i8_zero_rows_scale_one():
    q, s = kq.quantize_rows_i8(jnp.zeros((3, 4), jnp.float32))
    np.testing.assert_array_equal(np.asarray(s), np.ones((3, 1), np.float32))
    np.testing.assert_array_equal(np.asarray(q), np.zeros((3, 4), np.int8))


@pytest.mark.parametrize("quant,dtype", [("bf16", jnp.bfloat16),
                                         ("int8", jnp.int8)])
def test_quant_replica_dtypes_and_stacked(quant, dtype):
    rng = np.random.default_rng(1)
    vecs = jnp.asarray(rng.standard_normal((2, 16, 8)), jnp.float32)
    qv, qs = kq.quant_replica(vecs, quant)
    assert qv.dtype == dtype and qv.shape == vecs.shape
    if quant == "int8":
        assert qs.shape == (2, 16, 1)
    else:
        assert qs is None


def test_quant_bytes_per_row_reduction():
    """The acceptance bar's byte accounting: bf16 halves, int8 ~quarters."""
    for d in (64, 128, 768):
        f32 = kq.quant_bytes_per_row(d, "none")
        assert f32 == 4 * d
        assert kq.quant_bytes_per_row(d, "bf16") * 2 == f32
        assert kq.quant_bytes_per_row(d, "int8") <= f32 / 2  # >= 2x smaller
    assert kq.quant_bytes_per_row(768, "int8") == 768 + 4


def test_engine_quants_pins_kernel_quants():
    """engine.QUANTS is a deliberate duplicate (no top-level kernels import
    in engine) — keep them identical."""
    assert eng.QUANTS == kq.QUANTS == ("none", "bf16", "int8")


def test_with_quant_replica_roundtrip():
    rng = np.random.default_rng(2)
    idx = KHIIndex.build(rng.standard_normal((64, 8)).astype(np.float32),
                         rng.uniform(0, 1, (64, 2)).astype(np.float32),
                         KHIConfig(M=8))
    di = eng.device_put_index(idx, quant="int8")
    assert di.qvecs is not None and di.qvecs.dtype == jnp.int8
    assert di.qscale.shape == (di.vecs.shape[0], 1)
    bare = eng.with_quant_replica(di, "none")
    assert bare.qvecs is None and bare.qscale is None
    with pytest.raises(ValueError, match="quant"):
        eng.with_quant_replica(di, "fp4")


@pytest.mark.parametrize("quant", ["none", "int8"])
def test_device_rows_pad_to_whole_tiles(quant):
    """Device rows pad to whole int8 HBM tiles (32 rows), so the gather
    kernels never copy the corpus; the pad rows are never answered, even
    for an unbounded box (their +inf attrs would pass it)."""
    from repro.core.query_ref import Predicate

    rng = np.random.default_rng(3)
    vecs = rng.standard_normal((70, 8)).astype(np.float32)
    idx = KHIIndex.build(vecs, rng.uniform(0, 1, (70, 2)).astype(np.float32),
                         KHIConfig(M=8))
    di = eng.device_put_index(idx, quant=quant)
    assert di.vecs.shape[0] == 96
    if quant == "int8":
        assert di.qvecs.shape[0] == di.qscale.shape[0] == 96
    assert np.all(np.asarray(di.nbrs)[70:] == -1)
    everything = [Predicate([-np.inf] * 2, [np.inf] * 2)] * 2
    p = eng.SearchParams(k=80, ef=96, strategy="scan", quant=quant,
                         backend="pallas_gather_l2_filter")
    ids, _, _ = eng.search_batch(di, vecs[:2], everything, p)
    np.testing.assert_array_equal(np.sort(ids[:, :70], axis=1),
                                  np.tile(np.arange(70), (2, 1)))
    assert np.all(ids[:, 70:] == -1)


# ----------------------------------------------- kernel vs oracle parity

@pytest.mark.parametrize("B,C,N,D,M", [(2, 8, 40, 8, 2), (3, 33, 200, 24, 3)])
def test_gather_l2_filter_q8_kernel_matches_ref(B, C, N, D, M):
    from repro.kernels.gather_l2_filter import gather_l2_filter_q8_blocked_raw
    corpus, attrs, q, qlo, qhi = _workload(B, N, D, M, seed=B + N)
    rng = np.random.default_rng(9)
    idx = rng.integers(-1, N, (B, C)).astype(np.int32)
    qv, qs = kq.quant_replica(jnp.asarray(corpus), "int8")
    got = gather_l2_filter_q8_blocked_raw(
        jnp.asarray(idx), qv, qs, jnp.asarray(attrs), jnp.asarray(q),
        jnp.asarray(qlo), jnp.asarray(qhi), c_blk=16, interpret=True)
    want = gather_l2_filter_q8_ref(jnp.asarray(idx), qv, qs,
                                   jnp.asarray(attrs), jnp.asarray(q),
                                   jnp.asarray(qlo), jnp.asarray(qhi))
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_array_equal(np.isinf(got), np.isinf(want))
    fin = np.isfinite(want)
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,N,D,M,k,n_blk", [(2, 100, 8, 2, 5, 32),
                                             (3, 300, 24, 3, 10, 64)])
def test_scan_topk_q8_kernel_ids_bitwise_vs_ref(B, N, D, M, k, n_blk):
    from repro.kernels.scan_topk import scan_topk_q8_raw
    corpus, attrs, q, qlo, qhi = _workload(B, N, D, M, seed=N + k)
    qv, qs = kq.quant_replica(jnp.asarray(corpus), "int8")
    gi, gd = scan_topk_q8_raw(qv, qs, jnp.asarray(attrs), jnp.asarray(q),
                              jnp.asarray(qlo), jnp.asarray(qhi), k=k,
                              n_blk=n_blk, interpret=True)
    wi, wd = scan_topk_q8_ref(qv, qs, jnp.asarray(attrs), jnp.asarray(q),
                              jnp.asarray(qlo), jnp.asarray(qhi), k)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    fin = np.isfinite(np.asarray(wd))
    np.testing.assert_allclose(np.asarray(gd)[fin], np.asarray(wd)[fin],
                               rtol=1e-5, atol=1e-5)


def test_ops_wrappers_route_q8():
    from repro.kernels import ops
    corpus, attrs, q, qlo, qhi = _workload(2, 50, 8, 2, seed=5)
    qv, qs = kq.quant_replica(jnp.asarray(corpus), "int8")
    gi, gd = ops.scan_topk_q8(qv, qs, jnp.asarray(attrs), jnp.asarray(q),
                              jnp.asarray(qlo), jnp.asarray(qhi), k=4)
    wi, _ = scan_topk_q8_ref(qv, qs, jnp.asarray(attrs), jnp.asarray(q),
                             jnp.asarray(qlo), jnp.asarray(qhi), 4)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    idx = jnp.asarray(np.arange(8, dtype=np.int32)[None].repeat(2, 0))
    d1 = ops.gather_l2_filtered_q8(idx, qv, qs, jnp.asarray(attrs),
                                   jnp.asarray(q), jnp.asarray(qlo),
                                   jnp.asarray(qhi))
    d2 = gather_l2_filter_q8_ref(idx, qv, qs, jnp.asarray(attrs),
                                 jnp.asarray(q), jnp.asarray(qlo),
                                 jnp.asarray(qhi))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=1e-5, atol=1e-5)


# -------------------------------------------------- engine rerank contract

def _oracle_topk(corpus, attrs, q, qlo, qhi, k):
    i, d = scan_topk_ref(jnp.asarray(corpus), jnp.asarray(attrs),
                         jnp.asarray(q), jnp.asarray(qlo),
                         jnp.asarray(qhi), k)
    return np.asarray(i), np.asarray(d)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("quant", ["bf16", "int8"])
def test_scan_strategy_ids_bitwise_vs_f32_oracle(backend, quant):
    """Pinned smoke cases: the quantized scan + exact rerank must return
    ids bit-identical to the f32 oracle (the acceptance bar)."""
    corpus, attrs, q, qlo, qhi = _workload(6, 400, 16, 2, seed=42)
    qlo[0], qhi[0] = 0.0, 10.0                       # whole corpus
    qhi[1] = qlo[1] - 1.0                            # empty box
    idx = KHIIndex.build(corpus, attrs, KHIConfig(M=8))
    p = eng.SearchParams(k=8, ef=64, backend=backend, router="level",
                         strategy="scan", quant=quant)
    ids, dists, hops, _ = eng.Planner(idx, p).search(q, qlo, qhi)
    oid, od = _oracle_topk(corpus, attrs, q, qlo, qhi, 8)
    np.testing.assert_array_equal(ids, oid)
    fin = np.isfinite(od)
    np.testing.assert_allclose(dists[fin], od[fin], rtol=1e-5, atol=1e-6)
    assert np.all(hops == 0)


def test_rerank_fixes_k_boundary_inversion():
    """Find a seed where the RAW int8 scan order is wrong at the k
    boundary, then assert the reranked engine path returns the f32
    oracle's ids anyway — the rerank is load-bearing, not decorative."""
    k = 5
    inverted = None
    for seed in range(40):
        corpus, attrs, q, qlo, qhi = _workload(4, 256, 16, 2, seed=seed)
        qlo[:], qhi[:] = 0.0, 10.0                   # every row in range
        qv, qs = kq.quant_replica(jnp.asarray(corpus), "int8")
        ri, _ = scan_topk_q8_ref(qv, qs, jnp.asarray(attrs),
                                 jnp.asarray(q), jnp.asarray(qlo),
                                 jnp.asarray(qhi), k)
        oi, _ = _oracle_topk(corpus, attrs, q, qlo, qhi, k)
        if not np.array_equal(np.asarray(ri), oi):
            inverted = (corpus, attrs, q, qlo, qhi, oi)
            break
    assert inverted is not None, "no int8 k-boundary inversion in 40 seeds"
    corpus, attrs, q, qlo, qhi, oi = inverted
    idx = KHIIndex.build(corpus, attrs, KHIConfig(M=8))
    p = eng.SearchParams(k=k, ef=64, backend="jnp", router="level",
                         strategy="scan", quant="int8")
    ids, _, _, _ = eng.Planner(idx, p).search(q, qlo, qhi)
    np.testing.assert_array_equal(ids, oi)


@pytest.mark.parametrize("quant", ["bf16", "int8"])
def test_rerank_duplicate_ties_lowest_id(quant):
    """Duplicate rows have exactly equal f32 distances; the reranked
    (dist, id) order must list the lower id first on every path."""
    rng = np.random.default_rng(3)
    corpus = rng.standard_normal((64, 8)).astype(np.float32)
    corpus[41] = corpus[7]                            # exact duplicate pair
    attrs = rng.uniform(0, 1, (64, 2)).astype(np.float32)
    attrs[41] = attrs[7]
    q = corpus[7][None] + np.float32(0.01)
    qlo = np.zeros((1, 2), np.float32)
    qhi = np.ones((1, 2), np.float32)
    idx = KHIIndex.build(corpus, attrs, KHIConfig(M=8))
    p = eng.SearchParams(k=4, ef=32, backend="jnp", router="level",
                         strategy="scan", quant=quant)
    ids, dists, _, _ = eng.Planner(idx, p).search(q, qlo, qhi)
    oid, _ = _oracle_topk(corpus, attrs, q, qlo, qhi, 4)
    np.testing.assert_array_equal(ids, oid)
    pos7, pos41 = list(ids[0]).index(7), list(ids[0]).index(41)
    assert pos7 < pos41 and dists[0][pos7] == dists[0][pos41]


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("quant", ["bf16", "int8"])
def test_rerank_all_out_of_range_lanes(backend, quant):
    corpus, attrs, q, qlo, qhi = _workload(3, 120, 8, 2, seed=8)
    qlo[:], qhi[:] = 1.0, 0.0                        # provably empty boxes
    idx = KHIIndex.build(corpus, attrs, KHIConfig(M=8))
    p = eng.SearchParams(k=6, ef=32, backend=backend, router="level",
                         strategy="scan", quant=quant)
    ids, dists, _, _ = eng.Planner(idx, p).search(q, qlo, qhi)
    np.testing.assert_array_equal(ids, np.full((3, 6), -1, np.int32))
    assert np.all(np.isinf(dists))


@pytest.mark.parametrize("quant", ["bf16", "int8"])
def test_nan_tombstones_masked_through_quant_replica(quant):
    """A tombstoned row's quantized data stays in the replica, but its NaN
    attr row must keep it out of every quantized top-k (delete coherence
    without rewriting qvecs — DESIGN.md §12)."""
    rng = np.random.default_rng(4)
    corpus = rng.standard_normal((96, 8)).astype(np.float32)
    attrs = rng.uniform(0, 1, (96, 2)).astype(np.float32)
    q = corpus[10][None]                              # row 10 is the 1-NN
    qlo = np.zeros((1, 2), np.float32)
    qhi = np.ones((1, 2), np.float32)
    idx = KHIIndex.build(corpus, attrs, KHIConfig(M=8))
    p = eng.SearchParams(k=4, ef=32, backend="jnp", router="level",
                         strategy="scan", quant=quant)
    planner = eng.Planner(idx, p)
    ids0, _, _, _ = planner.search(q, qlo, qhi)
    assert 10 in ids0[0]
    import dataclasses as dc
    di = planner.index
    tomb = dc.replace(di, attrs=di.attrs.at[10].set(jnp.nan))
    planner.refresh_index(tomb)
    ids1, _, _, _ = planner.search(q, qlo, qhi)
    assert 10 not in ids1[0]
    masked = attrs.copy()
    masked[10] = np.nan
    oid, _ = _oracle_topk(corpus, masked, q, qlo, qhi, 4)
    np.testing.assert_array_equal(ids1, oid)


# --------------------------------------------------------------- guards

def test_quant_param_validation():
    with pytest.raises(ValueError, match="quant"):
        eng.SearchParams(quant="fp4")
    with pytest.raises(ValueError, match="rerank_mult"):
        eng.SearchParams(rerank_mult=0)
    with pytest.raises(ValueError, match="node_scan_threshold"):
        eng.SearchParams(node_scan_threshold=-1)
    # backend compatibility is a strategy-combo rule, enforced by every
    # runtime entry point through validate_search_params
    with pytest.raises(ValueError, match="quant"):
        eng._check_strategy_combo(
            eng.SearchParams(backend="pallas_l2", quant="int8"))
    with pytest.raises(ValueError, match="dist_fn"):
        eng.resolve_scorer("jnp", dist_fn=lambda a, b: 0.0, quant="int8")
