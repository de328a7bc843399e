"""Serving launcher: batched RFANNS retrieval + optional LM generation.

``python -m repro.launch.serve --mode khi`` stands up a ``KHIService``
(micro-batching + shard fan-out + result cache, DESIGN.md §3) and drives it
with a stream of mixed-size request bursts — the serving workload, not just
a fixed-batch loop. ``--shards S`` serves a sharded corpus, ``--backend``
picks the scoring backend (default ``pallas_gather_l2_filter``, the
predicate-fused kernel of ``configs/khi_serve.py``; ``jnp`` runs no
kernel), ``--router`` the Phase-A tree router,
``--strategy`` the execution strategy (``auto`` = per-query planner
dispatch between graph search and the exact brute scan, DESIGN.md §10;
``--scan-threshold`` overrides the derived dispatch threshold);
``--mesh`` serves the sharded corpus through the collective shard_map
pipeline on a ``(data, model)`` query mesh (DESIGN.md §14);
``--stream-smoke`` additionally exercises the streaming write path
(insert → delete → compact → re-query, DESIGN.md §11) and asserts that
post-compaction answers match the pre-compaction delta-merged answers;
``--load-smoke`` drives the SLO scheduler (DESIGN.md §13) with a bursty
open-loop replay under ``--inject`` fault injection — ``--slo-ms``,
``--qdepth`` and ``--degrade-ladder`` set the admission/degradation
policy — and asserts the no-silent-drop + retry accounting contract;
``--filter-expr 'a0 >= 3 and (a1 in [1, 4] or not a2 <= 0)'`` serves a
compiled boolean predicate (DESIGN.md §15) through
``KHIService.search_expr`` and differentially checks it against the
numpy mask-then-top-k oracle — bit-identical under ``--strategy scan``
(the CI gate), in-filter + overlap otherwise; ``--mode generate`` runs
prefill+decode on a smoke LM.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def serve_khi(args):
    from repro.core import KHIConfig, KHIIndex, SearchParams
    from repro.core.sharded import build_sharded
    from repro.data import DatasetSpec, make_dataset, make_queries
    from repro.serve import KHIService, Request, ServeConfig

    spec = DatasetSpec("serve", n=args.n, d=args.d, m=3, seed=0,
                       attr_kinds=("year", "lognormal", "uniform"),
                       attr_corr=0.6)
    vecs, attrs = make_dataset(spec)
    cfg = KHIConfig(M=16, builder="device")  # jitted on-device build (DESIGN.md §7)
    print(f"[serve] building KHI over n={args.n} d={args.d} "
          f"shards={args.shards}")
    if args.shards > 1 or args.mesh:
        index = build_sharded(vecs, attrs, max(args.shards, 1), cfg)
    else:
        index = KHIIndex.build(vecs, attrs, cfg)
    mesh = None
    if args.mesh:
        # collective serving (DESIGN.md §14): one shard per `model` device;
        # needs len(jax.devices()) >= shards (emulate with XLA_FLAGS)
        from repro.launch.mesh import make_query_mesh
        mesh = make_query_mesh(max(args.shards, 1), 1)
        print(f"[serve] collective mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    params = SearchParams(k=10, ef=args.ef, c_e=10, c_n=16,
                          backend=args.backend,
                          expand_width=args.expand_width,
                          router=args.router,
                          strategy=args.strategy,
                          scan_threshold=args.scan_threshold,
                          quant=args.quant,
                          rerank_mult=args.rerank_mult,
                          node_scan_threshold=args.node_scan_threshold,
                          box_budget=args.box_budget)
    buckets = tuple(sorted({1, 8, args.batch}))
    svc = KHIService(index, params, config=ServeConfig(buckets=buckets),
                     mesh=mesh)

    Q, preds = make_queries(vecs, attrs, n_queries=args.batch * args.iters,
                            sigma=1 / 16, seed=1)
    # warm the big-bucket trace with THROWAWAY queries (perturbed copies:
    # same shapes, different cache keys) so the timed stream below never
    # hits the cache, then stream mixed-size bursts through the
    # micro-batcher (what a real frontend sends)
    lo = np.stack([p.lo for p in preds]).astype(np.float32)
    hi = np.stack([p.hi for p in preds]).astype(np.float32)
    svc.search(Q[: args.batch] + np.float32(1e-3),
               lo[: args.batch], hi[: args.batch])
    reqs = (Request(Q[i], lo[i], hi[i]) for i in range(len(Q)))
    t0 = time.perf_counter()
    results = list(svc.serve_stream(reqs))
    dt = time.perf_counter() - t0
    snap = svc.snapshot()
    print(f"[serve] {len(results)} requests in {dt:.2f}s "
          f"({len(results)/dt:.0f} QPS end-to-end; "
          f"device {snap['device_qps'] and round(snap['device_qps'])} QPS)")
    print(f"[serve] backend={args.backend} E={args.expand_width} "
          f"router={args.router} strategy={args.strategy} "
          f"batches={snap['batches']} scan_lanes={snap['scan_lanes']} "
          f"pad_lanes={snap['pad_lanes']} cache_hits={snap['cache_hits']} "
          f"buckets={snap['traced_buckets']}")
    if args.filter_expr:
        filter_expr_smoke(svc, vecs, attrs, Q, args)
    if args.stream_smoke:
        stream_smoke(svc, vecs, attrs, Q, lo, hi, args)
    if args.load_smoke:
        load_smoke(svc, Q, lo, hi, args)


def filter_expr_smoke(svc, vecs, attrs, Q, args):
    """Compiled-predicate smoke (DESIGN.md §15): parse ``--filter-expr``,
    serve it through ``KHIService.search_expr``, and differentially
    check the answers against ``query_ref.brute_force_expr`` — the numpy
    mask-then-top-k oracle. Under ``--strategy scan`` every lane is
    exact, so ids must be bit-identical (what the CI step pins); under
    graph-family strategies the smoke asserts the in-filter guarantee
    and a recall floor instead (graph walks are approximate)."""
    from repro.core import brute_force_expr, eval_expr, parse_expr
    from repro.core.predicate import compile_expr

    m = attrs.shape[-1]
    expr = parse_expr(args.filter_expr, m)
    prog = compile_expr(expr, m, box_budget=args.box_budget)
    B = min(16, len(Q))
    k = svc.params.k
    t0 = time.perf_counter()
    ids, dists = svc.search_expr(Q[:B], expr)
    dt = time.perf_counter() - t0
    mask = eval_expr(expr, attrs)
    hits = ok = 0
    for i in range(B):
        ref_ids = brute_force_expr(vecs, attrs, Q[i], expr, k)
        got = ids[i][ids[i] >= 0]
        assert mask[got].all(), f"lane {i}: out-of-filter id served"
        if args.strategy == "scan":
            np.testing.assert_array_equal(
                got, ref_ids, err_msg=f"lane {i}: scan lanes must be "
                f"bit-identical to the oracle")
        hits += len(set(got.tolist()) & set(ref_ids.tolist()))
        ok += max(len(ref_ids), 1)
    recall = hits / ok
    assert recall >= (1.0 if args.strategy == "scan" else 0.6), \
        f"filter-expr recall {recall:.2f}"
    snap = svc.snapshot()
    print(f"[serve] filter-expr: {args.filter_expr!r} -> {prog.mode} "
          f"program ({prog.n_boxes} boxes, budget {args.box_budget}); "
          f"{B} queries in {dt * 1e3:.0f}ms, recall {recall:.2f}, "
          f"predicate_lanes={snap['predicate_lanes']}")


def stream_smoke(svc, vecs, attrs, Q, lo, hi, args):
    """Streaming write-path smoke (DESIGN.md §11): insert perturbed copies,
    delete a mix of base + fresh rows, query the delta-merged view, then
    compact and assert the published epoch answers the same queries with
    the same ids (exactly, on scan-served lanes; the CI step runs
    --strategy scan so every lane is exact)."""
    rng = np.random.default_rng(7)
    svc.enable_streaming(capacity=args.delta_capacity)
    t0 = time.perf_counter()
    sel = rng.choice(len(vecs), size=64, replace=False)
    exts = svc.insert(vecs[sel] + np.float32(1e-3), attrs[sel])
    dele = np.concatenate([exts[:16], sel[:16]])   # fresh + base rows
    n_del = svc.delete(dele)
    ingest_dt = time.perf_counter() - t0
    B = min(16, len(Q))
    pre_ids, pre_d = svc.search(Q[:B], lo[:B], hi[:B])
    svc.compact()
    post_ids, post_d = svc.search(Q[:B], lo[:B], hi[:B])
    if args.strategy == "scan":
        np.testing.assert_array_equal(post_ids, pre_ids)
        np.testing.assert_allclose(post_d, pre_d, rtol=1e-5)
        verdict = "bit-identical"
    else:
        agree = float((post_ids == pre_ids).mean())
        assert agree > 0.5, f"pre/post-compaction overlap {agree:.2f}"
        verdict = f"overlap {agree:.2f} (graph lanes are approximate)"
    snap = svc.snapshot()
    print(f"[serve] stream-smoke: +{len(exts)} inserts -{n_del} deletes "
          f"in {ingest_dt * 1e3:.0f}ms, compactions="
          f"{snap['compactions']} n_live={snap['n_live']} "
          f"epoch={snap['epoch']}; pre/post-compaction answers {verdict}")


def load_smoke(svc, Q, lo, hi, args):
    """SLO-scheduler smoke under fault injection (DESIGN.md §13): drive
    a short bursty open-loop replay through ``SLOScheduler`` with the
    ``--inject`` faults armed plus one forced deadline breach, then
    assert the §13 accounting contract — zero silent drops, tier
    accounting sums to the served total, and the scheduler's injected
    fault/retry counters reconcile one-for-one with the injector's
    firing log. This is the CI gate for the recovery layer."""
    from repro.serve import (FaultInjector, Rejected, Request,
                             SchedulerConfig, Served, SLOScheduler,
                             TierSpec, replay_open_loop)

    injector = FaultInjector.parse(args.inject)
    cfg = SchedulerConfig(
        qdepth=args.qdepth, slo_ms=args.slo_ms,
        ladder=TierSpec.parse_ladder(args.degrade_ladder))
    sched = SLOScheduler(svc, cfg, injector=injector, autostart=True)
    # warm every tier's bucket shapes outside the replay (compiles would
    # otherwise dominate the smoke's latencies and trip deadlines)
    for t in range(svc.n_tiers):
        for b in svc.config.buckets:
            svc.search(Q[:b] + np.float32(2e-3), lo[:b], hi[:b], tier=t)

    n = min(48, len(Q))
    reqs = [Request(Q[i], lo[i], hi[i]) for i in range(n)]
    # bursty arrivals: a trickle, then half the stream at one instant
    arrivals = [i * 0.01 for i in range(n // 2)]
    arrivals += [arrivals[-1]] * (n - n // 2)
    tickets = replay_open_loop(
        lambda r: sched.submit(r[1], tenant=f"t{r[0] % 2}"),
        arrivals, list(enumerate(reqs)))
    # one forced deadline breach: dead on arrival -> typed "expired"
    t_doa = sched.submit(reqs[0], deadline_ms=0)
    snap = sched.shutdown(drain=True)
    recs = [sched.result(t, timeout=0) for t in tickets]

    fired = injector.counts()
    n_served = sum(isinstance(r, Served) for r in recs)
    n_rej = sum(isinstance(r, Rejected) for r in recs)
    assert isinstance(sched.result(t_doa, timeout=0), Rejected)
    assert snap["dropped"] == 0, f"silent drop: {snap}"
    assert n_served + n_rej == n, "missing terminal record"
    assert sum(snap["tier_served"].values()) == snap["served"], \
        f"tier accounting != served total: {snap}"
    assert snap["rejected"].get("expired", 0) >= 1, \
        "forced deadline breach not recorded"
    assert snap["injected_faults"] == fired["device_error"], \
        f"scheduler saw {snap['injected_faults']} injected faults, " \
        f"injector fired {fired['device_error']}"
    assert snap["retries"] == snap["batch_failures"], \
        "every failed batch must get exactly one re-split retry pass"
    if any(s.kind == "device_error" and s.step is not None
           for s in injector.specs):
        assert snap["batch_failures"] >= 1, "induced batch failure missed"
        assert all(isinstance(r, Served) for r in recs), \
            "transient device_error must recover every lane via re-split"
    print(f"[serve] load-smoke: {n + 1} submitted = {snap['served']} served"
          f" + {sum(snap['rejected'].values())} rejected (0 dropped); "
          f"tiers={snap['tier_served']} retries={snap['retries']} "
          f"faults={fired} timeouts={snap['timeouts']} slo={args.slo_ms}ms")


def serve_generate(args):
    from repro.configs import get_smoke_config
    from repro.models import model as M

    cfg = get_smoke_config(args.arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 4, 32
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), dtype=jnp.int32)
    cache = M.init_cache(cfg, B, S + args.new_tokens)
    step = jax.jit(lambda p, c, t, pos: M.decode_step(p, cfg, c, t, pos))
    toks = prompt
    # teacher-forced prefill through the decode path (exercises the cache)
    for t in range(S):
        logits, cache = step(params, cache, toks[:, t: t + 1], jnp.int32(t))
    out = []
    cur = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    t0 = time.perf_counter()
    for t in range(S, S + args.new_tokens):
        out.append(np.asarray(cur))
        logits, cache = step(params, cache, cur, jnp.int32(t))
        cur = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    dt = time.perf_counter() - t0
    gen = np.concatenate(out, axis=1)
    print(f"[serve] generated {gen.shape} tokens, "
          f"{args.new_tokens * B / dt:.1f} tok/s; sample: {gen[0][:16]}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["khi", "generate"], default="khi")
    ap.add_argument("--arch", default="qwen1.5-4b")
    ap.add_argument("--n", type=int, default=5000)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--ef", type=int, default=64)
    ap.add_argument("--iters", type=int, default=3)
    from repro.core.engine import BACKENDS, ROUTERS

    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--mesh", action="store_true",
                    help="serve through the collective shard_map pipeline "
                         "on a (1, shards) (data, model) query mesh "
                         "(DESIGN.md §14) — needs at least --shards "
                         "devices; emulate on CPU with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    from repro.configs.khi_serve import config as khi_serve

    # the production scorer (configs/khi_serve.py): a Pallas kernel, run
    # by Mosaic on a TPU and by the interpreter elsewhere
    ap.add_argument("--backend", default=khi_serve().backend,
                    choices=list(BACKENDS))
    ap.add_argument("--expand-width", type=int, default=1,
                    help="frontier width E: pool entries expanded per hop")
    ap.add_argument("--router", default="level", choices=list(ROUTERS),
                    help="Phase-A tree router (level = batched sweep)")
    from repro.core.engine import STRATEGIES

    ap.add_argument("--strategy", default="auto", choices=list(STRATEGIES),
                    help="execution strategy: graph | scan (exact brute "
                         "scan) | auto (per-query planner dispatch — the "
                         "serving default, as in configs/khi_serve.py) | "
                         "hybrid (per-node windowed scan + graph walk, "
                         "DESIGN.md §12)")
    ap.add_argument("--scan-threshold", type=int, default=0,
                    help="auto-dispatch threshold in in-range objects "
                         "(0 = derive DEFAULT_SCAN_FRAC of the corpus)")
    from repro.core.engine import QUANTS

    ap.add_argument("--quant", default="none", choices=list(QUANTS),
                    help="quantized score path (DESIGN.md §12): stream a "
                         "bf16/int8 corpus replica and rerank the "
                         "over-fetched top k*rerank_mult exactly in f32")
    ap.add_argument("--rerank-mult", type=int, default=4,
                    help="quantized over-fetch factor before the exact "
                         "f32 rerank")
    ap.add_argument("--node-scan-threshold", type=int, default=0,
                    help="hybrid per-node scan threshold in rows "
                         "(0 = inherit the resolved scan threshold)")
    ap.add_argument("--filter-expr", default="",
                    help="boolean predicate to serve through the "
                         "predicate compiler (DESIGN.md §15), e.g. "
                         "'a0 >= 2015 and (a1 in [1, 4] or a2 > 0.5)'; "
                         "checked against the numpy oracle "
                         "(bit-identical under --strategy scan)")
    ap.add_argument("--box-budget", type=int, default=8,
                    help="max disjoint boxes a compiled predicate may "
                         "lower to before the dense bitmask fallback")
    ap.add_argument("--slo-ms", type=float, default=250.0,
                    help="default per-request deadline for the SLO "
                         "scheduler (DESIGN.md §13)")
    ap.add_argument("--qdepth", type=int, default=64,
                    help="bounded admission-queue depth; over-capacity "
                         "requests get a typed queue_full rejection")
    ap.add_argument("--degrade-ladder",
                    default="ef=16,ef=8+expand_width=1",
                    help="degradation-tier ladder, comma-separated steps "
                         "of +-joined SearchParams overrides, e.g. "
                         "'ef=32,ef=16+expand_width=1' (DESIGN.md §13)")
    ap.add_argument("--inject", default="",
                    help="fault-injection spec for --load-smoke, e.g. "
                         "'device_error@1,latency:30ms@2' "
                         "(serve/faults.py grammar)")
    ap.add_argument("--load-smoke", action="store_true",
                    help="drive the SLO scheduler with a bursty replay "
                         "under --inject faults and assert the §13 "
                         "no-drop/retry accounting contract")
    ap.add_argument("--stream-smoke", action="store_true",
                    help="exercise the streaming write path: insert -> "
                         "delete -> compact -> re-query (DESIGN.md §11)")
    ap.add_argument("--delta-capacity", type=int, default=256,
                    help="per-shard delta-segment rows before inserts "
                         "force a compaction")
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    if args.mode == "khi":
        serve_khi(args)
    else:
        serve_generate(args)


if __name__ == "__main__":
    main()
