"""Device builder parity: the jitted bulk builder must reproduce the numpy
bulk builder bit-for-bit on fixed seeds (same exact top-ef_b candidates,
same RNG-prune decisions), and a device-built index must serve the tier-1
synthetic workload at recall parity with the incremental (paper Alg. 5)
build. Bit-equality across independent float pipelines holds because every
selection/shielding comparison has margin >> cross-backend rounding at
these seeds (decision-margin measured at ~1e-6 relative; backend rounding
is ~1e-7) — the fixed seeds pin that."""

import numpy as np
import pytest

from repro.core import hnsw
from repro.core import query_ref as qr
from repro.core.build_device import build_graphs_device
from repro.core.khi import KHIConfig, KHIIndex
from repro.core.tree import build_tree


def _random_case(n, d, m, seed):
    rng = np.random.default_rng(seed)
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    attrs = rng.random((n, m)).astype(np.float32)
    return vecs, attrs, build_tree(attrs)


@pytest.mark.parametrize("n,d,m,M,ef_b,seed", [
    (600, 16, 2, 8, None, 1),
    (900, 24, 3, 8, None, 0),
    (700, 24, 3, 8, 24, 0),      # custom ef_b (same value both builders)
])
def test_device_bitwise_matches_numpy_bulk(n, d, m, M, ef_b, seed):
    vecs, attrs, tree = _random_case(n, d, m, seed)
    ref = hnsw.build_graphs_bulk(tree, vecs, M=M, ef_b=ef_b)
    dev = build_graphs_device(tree, vecs, M=M, ef_b=ef_b)
    np.testing.assert_array_equal(dev, ref)


def test_row_blocked_large_node_path_matches():
    """Forcing every sizable node through the row-blocked program must not
    change a single row (rows are independent in the bulk formulation)."""
    vecs, attrs, tree = _random_case(700, 24, 3, 0)
    ref = hnsw.build_graphs_bulk(tree, vecs, M=8)
    dev = build_graphs_device(tree, vecs, M=8, large_node=256, row_block=128)
    np.testing.assert_array_equal(dev, ref)


def test_distance_block_cap_shrinks_row_block(monkeypatch):
    """A large node whose (row block, C) distance block would pass the cap
    runs in smaller row blocks, with not a single row changed."""
    from repro.core import build_device

    vecs, attrs, tree = _random_case(700, 24, 3, 0)
    ref = hnsw.build_graphs_bulk(tree, vecs, M=8)
    # C = 1024 at the root: a 64 KiB cap leaves 16-row blocks
    monkeypatch.setattr(build_device, "_BLOCK_BYTES", 1 << 16)
    dev = build_graphs_device(tree, vecs, M=8, large_node=256, row_block=128)
    np.testing.assert_array_equal(dev, ref)


def test_khi_config_device_builder_end_to_end(tiny_data):
    """KHIConfig(builder="device") == builder="bulk" through KHIIndex.build
    (the acceptance contract), and the bf16 matmul variant still yields a
    structurally valid graph."""
    vecs, attrs = tiny_data
    cfg_kw = dict(M=16, tau=3.0, leaf_capacity=2)
    bulk = KHIIndex.build(vecs, attrs, KHIConfig(builder="bulk", **cfg_kw))
    dev = KHIIndex.build(vecs, attrs, KHIConfig(builder="device", **cfg_kw))
    np.testing.assert_array_equal(dev.nbrs, bulk.nbrs)
    assert dev.config.builder == "device"
    assert dev.build_seconds > 0

    bf16 = build_graphs_device(dev.tree, vecs, M=16,
                               matmul_dtype="bfloat16")
    assert bf16.shape == bulk.nbrs.shape
    occupied = (bf16 >= 0).sum(axis=-1)
    assert occupied.max() <= 16
    # same rows defined (graph structure intact), contents may differ in bf16
    assert ((bf16 >= 0).any(axis=-1) == (bulk.nbrs >= 0).any(axis=-1)).all()


def test_device_built_recall_parity(tiny_data, tiny_index, tiny_queries):
    """A device-built index must serve the tier-1 workload within tolerance
    of the incremental (paper) build — graph construction quality, not just
    structural validity."""
    vecs, attrs = tiny_data
    dev = KHIIndex.build(vecs, attrs, KHIConfig(M=16, builder="device"))
    Q, preds = tiny_queries

    def mean_recall(index):
        recalls = []
        for q, p in zip(Q, preds):
            gt = qr.brute_force(index.vecs, index.attrs, q, p, 10)
            if not len(gt):
                continue
            got = qr.query(index, q, p, 10, ef=96)
            recalls.append(len(set(gt.tolist()) & set(got.tolist()))
                           / min(10, len(gt)))
        return float(np.mean(recalls))

    r_inc = mean_recall(tiny_index)
    r_dev = mean_recall(dev)
    assert r_dev >= r_inc - 0.05, f"device {r_dev:.3f} vs incr {r_inc:.3f}"
    assert r_dev >= 0.85


def test_build_sharded_default_is_device(tiny_data):
    """build_sharded's default config routes every shard through the device
    builder; the per-shard planes equal the numpy bulk builder's."""
    from repro.core.sharded import build_sharded, search_sharded_emulated
    from repro.core.engine import SearchParams
    from repro.data import make_queries

    vecs, attrs = tiny_data
    skhi_dev = build_sharded(vecs, attrs, 2, KHIConfig(M=16, builder="device"))
    skhi_bulk = build_sharded(vecs, attrs, 2, KHIConfig(M=16, builder="bulk"))
    np.testing.assert_array_equal(np.asarray(skhi_dev.di.nbrs),
                                  np.asarray(skhi_bulk.di.nbrs))

    # and the default config end-to-end: build + emulated fan-out search
    skhi = build_sharded(vecs, attrs, 2)
    Q, preds = make_queries(vecs, attrs, n_queries=4, sigma=1 / 16, seed=11)
    qlo = np.stack([p.lo for p in preds])
    qhi = np.stack([p.hi for p in preds])
    mi, md, _ = search_sharded_emulated(skhi, Q, qlo, qhi,
                                        SearchParams(k=5, ef=32, c_n=16))
    mi = np.asarray(mi)
    for i, p in enumerate(preds):
        got = mi[i][mi[i] >= 0]
        assert all(p.matches(attrs[g]) for g in got)
