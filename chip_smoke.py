#!/usr/bin/env python3
"""Smoke run of the KHI serving path on a TPU.

One chip (the default): builds one khi-serve shard (``configs/khi_serve.py``:
d=768, m=4, M=32; n=524,288 by default, see below) from a seeded
``make_dataset``
corpus with the device builder, then serves micro-batches at bucket 32
through ``KHIService.search`` under the production ``SearchParams``
(predicate-fused Pallas scorer, strategy="auto", expand_width=4, level
router). Half of every batch has wide boxes (graph lanes), half has boxes
far under the 10% scan threshold (scan lanes). Every answer is checked
against an exact float64 brute-force oracle: each id satisfies its box,
scan lanes are the exact top-10 up to distance ties, graph lanes reach
mean recall@10 >= 0.8.

The shard holds 524,288 rows and not khi-serve's 1,000,000: the device
build is quadratic in the largest node (exact top-K over each tree node's
pool), and at 1M rows it alone took 741 s of the smoke's 1200 s on a TPU
v5e. ``--n 1000000`` runs the full shard.

``--chips 4``: builds four shards with ``build_sharded``, serves the same
kind of batches through ``KHIService(..., mesh=make_query_mesh(4, 1))`` and
compares them id for id with ``search_sharded_emulated`` on one device.

The last line of standard output is one JSON object naming the device;
it is printed only when every check passed. Any failure exits non-zero.
The timings printed are those of a smoke run, not a benchmark.

    python chip_smoke.py [--n N] [--chips 4 [--n-shard N]]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

K = 10
BUCKET = 32
SCAN_SIGMA = 0.005     # scan-lane box selectivity, far under the 10% rule
GRAPH_SIGMA = 0.5      # graph-lane box selectivity
RECALL_FLOOR = 0.8
INTERPRET = False      # Mosaic kernels; the Pallas interpreter is for the CPU


def log(*a) -> None:
    print("[chip_smoke]", *a, flush=True)


def make_corpus(n: int, seed: int):
    from repro.data import DatasetSpec, make_dataset

    # Youtube-shaped attributes (year + three correlated log-normals, the
    # skewed kind the tree's exclusion rule exists for), khi-serve widths;
    # within-cluster spread of intrinsic dimension 16 (DatasetSpec)
    spec = DatasetSpec("khi-serve-shard", n=n, d=768, m=4, n_clusters=64,
                       attr_kinds=("year", "lognormal", "lognormal",
                                   "lognormal"),
                       attr_corr=0.85, latent_dim=16, seed=seed)
    return make_dataset(spec)


def make_batches(vecs, attrs, n_batches: int, seed: int):
    """``n_batches`` batches of BUCKET lanes: even lanes wide (graph),
    odd lanes narrow (scan). Returns Q, lo, hi (n_batches*BUCKET, ...) and
    the (n_batches*BUCKET,) bool mask of narrow lanes."""
    from repro.data import make_queries

    half = n_batches * BUCKET // 2
    qs, preds = [], []
    for sigma, s in ((GRAPH_SIGMA, seed + 1), (SCAN_SIGMA, seed + 2)):
        q, p = make_queries(vecs, attrs, n_queries=half, sigma=sigma, seed=s)
        qs.append(q)
        preds.append(p)
    Q = np.empty((2 * half, vecs.shape[1]), np.float32)
    Q[0::2], Q[1::2] = qs
    boxes = [b for pair in zip(*preds) for b in pair]
    lo = np.stack([b.lo for b in boxes]).astype(np.float32)
    hi = np.stack([b.hi for b in boxes]).astype(np.float32)
    narrow = np.zeros(2 * half, bool)
    narrow[1::2] = True
    return Q, lo, hi, narrow


def oracle(vecs, attrs, Q, lo, hi, k: int):
    """Exact in-box top-k by float64 squared L2: ids (B, k) -1 padded and
    distances (B, k) inf padded."""
    B = Q.shape[0]
    q64 = Q.astype(np.float64)
    qn = np.sum(q64 * q64, axis=1)
    best_d = np.full((B, k), np.inf)
    best_i = np.full((B, k), -1, np.int64)
    step = 1 << 17
    for s in range(0, vecs.shape[0], step):
        x = vecs[s:s + step].astype(np.float64)
        a = attrs[s:s + step]
        d = (np.sum(x * x, axis=1)[None, :] - 2.0 * (q64 @ x.T)
             + qn[:, None])
        inbox = np.all((a[None] >= lo[:, None]) & (a[None] <= hi[:, None]),
                       axis=-1)
        d = np.where(inbox, np.maximum(d, 0.0), np.inf)
        cd = np.concatenate([best_d, d], axis=1)
        ci = np.concatenate(
            [best_i, np.broadcast_to(np.arange(s, s + x.shape[0]),
                                     d.shape)], axis=1)
        sel = np.argpartition(cd, k, axis=1)[:, :k]
        sel = np.take_along_axis(
            sel, np.argsort(np.take_along_axis(cd, sel, axis=1), axis=1),
            axis=1)
        best_d = np.take_along_axis(cd, sel, axis=1)
        best_i = np.where(np.isfinite(best_d),
                          np.take_along_axis(ci, sel, axis=1), -1)
    return best_i, best_d


def exact_d64(vecs, Q, ids):
    """float64 squared L2 of ``ids`` (B, k) against their queries; inf
    where id is -1."""
    x = vecs[np.maximum(ids, 0)].astype(np.float64)
    d = np.sum((x - Q[:, None, :].astype(np.float64)) ** 2, axis=-1)
    return np.where(ids >= 0, d, np.inf)


def check_in_box(ids, attrs, lo, hi) -> int:
    """Number of returned ids that violate their box (must be 0); also
    fails on duplicate ids within a lane."""
    bad = 0
    for i in range(ids.shape[0]):
        got = ids[i][ids[i] >= 0]
        a = attrs[got]
        bad += int((~np.all((a >= lo[i]) & (a <= hi[i]), axis=1)).sum())
        bad += len(got) - len(set(got.tolist()))
    return bad


def check_exact(ids, vecs, Q, gt_d, rtol: float = 1e-5) -> int:
    """Lanes whose returned ids are not the exact top-k: the k returned
    distances (recomputed in float64) must equal the oracle's k smallest,
    each to float32 resolution — a returned id may differ from the
    oracle's only where their distances tie."""
    got = np.sort(exact_d64(vecs, Q, ids), axis=1)
    fin = np.isfinite(gt_d)
    same_pad = np.all(np.isfinite(got) == fin, axis=1)
    close = np.all(np.where(fin, np.abs(got - gt_d)
                            <= rtol * np.maximum(gt_d, 1.0), True), axis=1)
    return int((~(same_pad & close)).sum())


def recall(ids, gt_i) -> np.ndarray:
    out = []
    for g, t in zip(ids, gt_i):
        t = t[t >= 0]
        out.append(len(set(g[g >= 0].tolist()) & set(t.tolist()))
                   / max(len(t), 1))
    return np.asarray(out)


def serve_params(n_total: int):
    from repro.configs.khi_serve import config

    p = config().search_params()
    # the production 10% scan rule, at this corpus size
    return dataclasses.replace(p, k=K, scan_threshold=max(1, n_total // 10))


def assert_kernels(svc, batch: int) -> None:
    """Every whole-batch serving program must hold Mosaic custom calls:
    proof that the Pallas kernels were lowered, not interpreted."""
    hlo = svc.compiled_hlo(batch)
    for name, text in hlo.items():
        n = text.count("tpu_custom_call")
        log(f"program {name!r} at {batch} lanes: {n} tpu_custom_call ops")
        if n == 0:
            raise SystemExit(f"program {name!r} has no Mosaic kernel")


def one_chip(args) -> None:
    import jax

    from repro.core.khi import KHIConfig, KHIIndex
    from repro.serve import KHIService, ServeConfig

    n = args.n
    t = time.perf_counter()
    vecs, attrs = make_corpus(n, args.seed)
    log(f"corpus n={n} d={vecs.shape[1]} m={attrs.shape[1]} made in "
        f"{time.perf_counter() - t:.1f}s")

    t = time.perf_counter()
    index = KHIIndex.build(vecs, attrs, KHIConfig(M=32, builder="device"),
                           verbose=True)
    build_s = time.perf_counter() - t
    log(f"build n={n} d={index.d} H={index.height} M={index.nbrs.shape[2]}"
        f" nodes={index.tree.num_nodes}: {build_s:.2f}s")

    params = serve_params(n)
    t = time.perf_counter()
    svc = KHIService(index, params,
                     config=ServeConfig(buckets=(BUCKET,), cache_size=0),
                     interpret=INTERPRET)
    log(f"service up in {time.perf_counter() - t:.1f}s: backend="
        f"{svc.params.backend} strategy={svc.params.strategy} "
        f"expand_width={svc.params.expand_width} router={svc.params.router}"
        f" scan_threshold={svc.params.scan_threshold}")

    Q, lo, hi, narrow = make_batches(vecs, attrs, args.batches, args.seed)
    ids = np.empty((Q.shape[0], K), np.int64)
    dists = np.empty((Q.shape[0], K), np.float32)
    walls = []
    for b in range(args.batches):
        sl = slice(b * BUCKET, (b + 1) * BUCKET)
        t = time.perf_counter()
        ids[sl], dists[sl] = svc.search(Q[sl], lo[sl], hi[sl])
        walls.append(time.perf_counter() - t)
        log(f"batch {b}: {walls[-1]:.2f}s")
    warm = walls[1:]
    log(f"first batch (compiles) {walls[0]:.2f}s; warm batch wall "
        f"(smoke, not a benchmark) median {np.median(warm) * 1e3:.1f}ms "
        f"over {len(warm)} batches of {BUCKET}")
    snap = svc.snapshot()
    log(f"scan_lanes={snap['scan_lanes']} of {Q.shape[0]} lanes "
        f"({int(narrow.sum())} narrow by construction)")

    # a mixed batch splits into two 16-lane sub-batches (engine.Planner)
    assert_kernels(svc, BUCKET // 2)

    t = time.perf_counter()
    gt_i, gt_d = oracle(vecs, attrs, Q, lo, hi, K)
    log(f"float64 oracle in {time.perf_counter() - t:.1f}s")
    bad_box = check_in_box(ids, attrs, lo, hi)
    bad_scan = check_exact(ids[narrow], vecs, Q[narrow], gt_d[narrow])
    rec = recall(ids[~narrow], gt_i[~narrow])
    d_err = np.abs(exact_d64(vecs, Q, ids) - dists)
    d_err = float(np.max(np.where(ids >= 0, d_err, 0.0)))
    log(f"in-box violations={bad_box}; scan lanes not exact={bad_scan} of "
        f"{int(narrow.sum())}; graph recall@{K} mean={rec.mean():.4f} "
        f"min={rec.min():.2f} over {len(rec)} lanes; max |dist - f64| "
        f"{d_err:.3g}")
    peak = jax.devices()[0].memory_stats() or {}
    log(f"peak device memory {peak.get('peak_bytes_in_use', 0) / 2**30:.2f}"
        f" GiB of {peak.get('bytes_limit', 0) / 2**30:.2f} GiB")

    fails = []
    if bad_box:
        fails.append(f"{bad_box} returned ids outside their box")
    if snap["scan_lanes"] != int(narrow.sum()):
        fails.append(f"scan_lanes {snap['scan_lanes']} != "
                     f"{int(narrow.sum())} narrow lanes")
    if bad_scan:
        fails.append(f"{bad_scan} scan lanes differ from the exact top-{K}")
    if rec.mean() < RECALL_FLOOR:
        fails.append(f"graph recall {rec.mean():.4f} < {RECALL_FLOOR}")
    if fails:
        raise SystemExit("chip_smoke failed: " + "; ".join(fails))


def four_chips(args) -> None:
    from repro.core.khi import KHIConfig
    from repro.core.sharded import build_sharded, search_sharded_emulated
    from repro.launch.mesh import make_query_mesh
    from repro.serve import KHIService, ServeConfig

    S = 4
    n = S * args.n_shard
    vecs, attrs = make_corpus(n, args.seed)
    t = time.perf_counter()
    skhi = build_sharded(vecs, attrs, S, KHIConfig(M=32, builder="device"))
    log(f"build_sharded S={S} x n_shard={args.n_shard} d={vecs.shape[1]} "
        f"H={skhi.di.nbrs.shape[2]}: {time.perf_counter() - t:.2f}s")

    params = serve_params(n)
    svc = KHIService(skhi, params,
                     config=ServeConfig(buckets=(BUCKET,), cache_size=0),
                     mesh=make_query_mesh(S, 1), interpret=INTERPRET)
    Q, lo, hi, narrow = make_batches(vecs, attrs, args.batches, args.seed)
    t = time.perf_counter()
    ids, dists = svc.search(Q, lo, hi)
    log(f"collective search of {Q.shape[0]} lanes: "
        f"{time.perf_counter() - t:.2f}s (compiles)")
    assert_kernels(svc, BUCKET)
    e_ids, e_dists, _ = search_sharded_emulated(skhi, Q, lo, hi, svc.params,
                                                interpret=INTERPRET)
    e_ids, e_dists = np.asarray(e_ids), np.asarray(e_dists)
    same_i = int(np.sum(np.all(ids == e_ids, axis=1)))
    same_d = int(np.sum(np.all(dists == e_dists, axis=1)))
    bad_box = check_in_box(ids, attrs, lo, hi)
    log(f"collective vs search_sharded_emulated: ids equal on {same_i}/"
        f"{Q.shape[0]} lanes, dists bitwise equal on {same_d}/{Q.shape[0]};"
        f" in-box violations={bad_box}")
    if same_i != Q.shape[0] or same_d != Q.shape[0] or bad_box:
        raise SystemExit("chip_smoke --chips 4 failed")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--n", type=int, default=524_288,
                    help="rows of the one-chip shard")
    ap.add_argument("--n-shard", type=int, default=65_536,
                    help="rows per shard with --chips 4")
    ap.add_argument("--batches", type=int, default=4,
                    help=f"micro-batches of {BUCKET} lanes (first compiles)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compilation_cache

    log(f"device {devs[0].device_kind} x{len(devs)}; compile cache "
        f"{enable_compilation_cache()}")
    (four_chips if args.chips == 4 else one_chip)(args)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
