"""One benchmark run of one cell: set-up, the measured window, the check.

Everything that belongs to one configuration, traffic mix or per-layer
metric is data or a reader of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``<file>`` of the configuration (``bench/configs/<name>.json``);
* ``bench/traffic/<traffic>.json`` for the traffic mix;
* ``bench/metrics/<metric>.py`` for each per-layer metric, a module with
  ``read(ctx)`` that returns a number or None.

The system under test is ``KHIService`` over an index built with
``KHIIndex.build(..., builder="device")``; the benchmark takes nothing
else from the program but its spans, counters and program names.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import types

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(*a) -> None:
    print("[bench]", *a, file=sys.stderr, flush=True)


# --------------------------------------------------------------- the cell
def load_cell(workload: str):
    """(benchmark, cell, configuration, traffic) for a workload name."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return bench, cell, cfg, traffic


def metrics_for(entries: list, cell_name: str) -> list:
    return [m for m in entries
            if "workloads" not in m or cell_name in m["workloads"]]


def load_reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(kind: str) -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"({sorted(table)}): no peaks, no roofline")
    return table[kind]


class CompileCounter:
    """Counts the programs JAX compiles or loads from its cache, and the
    persistent-cache misses, through ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _ev(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ------------------------------------------------------------ end to end
def _e2e(name: str, w: dict):
    lat_ms = np.repeat([(b["t1"] - b["t0"]) * 1e3 for b in w["batches"]],
                       [b["lanes"] for b in w["batches"]])
    if name == "qps":
        return w["done"] / w["wall_s"]
    if name == "p95_ms":
        return float(np.percentile(lat_ms, 95))
    if name == "recall_at_10":
        return float(np.mean(w["recall"]))
    if name == "build_s":
        return w["build_s"]
    if name == "setup_s":
        return w["setup_s"]
    raise KeyError(f"no end-to-end metric named {name!r} in the harness")


# ------------------------------------------------------------------- run
def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, overrides: dict = None,
        interpret=None, fault=None) -> dict:
    """One run; returns the result line as a dict. ``overrides`` replaces
    configuration sizes (the CPU rehearsal's tiny n), ``fault`` wraps the
    timed ``search`` call (the fault tests)."""
    bench, cell, cfg, traffic = load_cell(workload)
    cfg = {**cfg, **(overrides or {})}
    import jax

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    if require_tpu and platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {platform!r}")
    if len(devs) < cell["chips"]:
        raise NoChip(f"cell asks for {cell['chips']} chips; JAX found "
                     f"{len(devs)}")
    peaks = load_peaks(kind) if require_tpu else None
    counter = CompileCounter()

    from . import gen, reference

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import khi_serve
    from repro.core.engine import device_put_index
    from repro.core.khi import KHIConfig, KHIIndex
    from repro.serve import KHIService

    parts = {"init": time.perf_counter() - t_start}
    t = time.perf_counter()
    vecs, attrs = gen.make_corpus(cfg, seed)
    parts["corpus"] = time.perf_counter() - t
    n, d, m = vecs.shape[0], vecs.shape[1], attrs.shape[1]
    B = int(traffic["batch"])
    n_warm = B * int(traffic["warm_batches"])
    t = time.perf_counter()
    Q, lo, hi, sel = gen.make_pool(vecs, attrs, traffic,
                                   n_warm + int(traffic["pool"]), seed,
                                   cfg["query_noise"])
    parts["pool"] = time.perf_counter() - t

    t = time.perf_counter()
    index = KHIIndex.build(vecs, attrs, KHIConfig(M=cfg["M"],
                                                  builder="device"))
    di = jax.block_until_ready(device_put_index(index))
    parts["build_s"] = time.perf_counter() - t
    del index

    t = time.perf_counter()
    serve = khi_serve.config()
    params = dataclasses.replace(
        serve.search_params(), **cfg["search"],
        scan_threshold=max(1, int(cfg["scan_frac"] * n)))
    svc = KHIService(di, params, config=serve.serve_config(),
                     interpret=interpret)
    parts["service"] = time.perf_counter() - t

    t = time.perf_counter()
    for b in range(int(traffic["warm_batches"])):
        sl = slice(b * B, (b + 1) * B)
        svc.search(Q[sl], lo[sl], hi[sl])
    # batches whose first g lanes ask for every row: the sub-batch shapes
    # a batch split between the two paths runs, should the planner's
    # routing bound send a few of the traffic's boxes past the scan rule
    for g in traffic.get("warm_wide_lanes", []):
        wlo, whi = lo[:B].copy(), hi[:B].copy()
        wlo[:g], whi[:g] = attrs.min(axis=0), attrs.max(axis=0)
        svc.search(Q[:B], wlo, whi)
    parts["warm"] = time.perf_counter() - t
    setup_compiles, setup_misses = counter.compiles, counter.misses
    search = svc.search if fault is None else fault(svc.search)

    # ---------------------------------------------------------- window
    import jax.profiler as prof

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    pool = slice(n_warm, None)
    Qp, lop, hip = Q[pool], lo[pool], hi[pool]
    out_ids = np.full((Qp.shape[0], params.k), -1, np.int64)
    out_d = np.full((Qp.shape[0], params.k), np.inf, np.float32)
    batches = []
    scan0 = svc.stats["scan_lanes"]
    if trace:
        # the benchmark's spans and the device; no Python call tracing,
        # which would slow the host's plan pass it measures
        opts = prof.ProfileOptions()
        opts.python_tracer_level = 0
        prof.start_trace(trace_dir, profiler_options=opts)
    t_win = time.perf_counter()
    setup_s = t_win - t_start
    i = 0
    while True:
        if i + B > Qp.shape[0]:
            raise RuntimeError(f"the window would exhaust the pool of "
                               f"{Qp.shape[0]} queries")
        sl = slice(i, i + B)
        t0 = time.perf_counter()
        with prof.TraceAnnotation("bench.search"):
            ids, dd = search(Qp[sl], lop[sl], hip[sl])
        t1 = time.perf_counter()
        with prof.TraceAnnotation("bench.record"):
            out_ids[sl], out_d[sl] = ids, dd
            scan1 = svc.stats["scan_lanes"]
            batches.append({"lanes": B, "scan_lanes": scan1 - scan0,
                            "t0": t0, "t1": t1})
            scan0 = scan1
            i += B
        if t1 - t_win >= seconds:
            break
    wall = time.perf_counter() - t_win
    if trace:
        prof.stop_trace()
    window_compiles = counter.compiles - setup_compiles
    mem = max((dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for dv in devs[:cell["chips"]])
    log(f"set-up {setup_s:.3f}s: " + ", ".join(
        f"{k} {v:.3f}s" for k, v in parts.items()))
    log(f"compiles in set-up {setup_compiles} (persistent-cache misses "
        f"{setup_misses}); compiles inside the window {window_compiles}")
    q = np.quantile(sel[n_warm:n_warm + i], [0, 0.25, 0.5, 0.75, 1])
    log(f"window {wall:.3f}s: {len(batches)} batches of {B}, {i} queries; "
        f"scan lanes {sum(b['scan_lanes'] for b in batches)}; "
        f"selectivity of the served boxes (min, quartiles, max) "
        + " ".join(f"{x:.4f}" for x in q))

    # the program's state goes before the reference runs
    svc = di = search = None
    gc.collect()

    t = time.perf_counter()
    Qw, low, hiw = Qp[:i], lop[:i], hip[:i]
    ref_ids, ref_d, cnt = reference.exact(vecs, attrs, Qw, low, hiw,
                                          params.k)
    checks, lane_fail, rec, gap = reference.judge(
        out_ids[:i], out_d[:i], vecs, attrs, Qw, low, hiw, ref_ids, ref_d,
        cnt, params.k, exact=bool(traffic.get("exact")),
        batches=[(b["lanes"], b["scan_lanes"]) for b in batches])
    log(f"reference and check {time.perf_counter() - t:.3f}s; recall@"
        f"{params.k} mean {rec.mean():.6f} min {rec.min():.3f}")
    if gap is not None:
        off = np.nonzero(gap > reference.EXACT_TOL)[0]
        log(f"answers not exact {len(off)} of {i}" + "".join(
            f"; lane {j} (batch {j // B}, service counted "
            f"{batches[j // B]['scan_lanes']} of {B} lanes scanned), "
            f"selectivity {sel[n_warm + j]:.4f}, rank gap {gap[j]:.6g}, "
            f"recall {rec[j]:.2f}" for j in off[:20]))
    log("per batch: wall_ms " + json.dumps(
        [round((b["t1"] - b["t0"]) * 1e3, 3) for b in batches])
        + " recall " + json.dumps(
            [round(float(rec[j * B:(j + 1) * B].mean()), 4)
             for j in range(len(batches))]))

    w = {"batches": batches, "done": i, "wall_s": wall, "recall": rec,
         "build_s": parts["build_s"], "setup_s": setup_s}
    result = {"correct": reference.passed(checks), "attempted": i,
              "failed": int(lane_fail.sum()), "metrics": {},
              "device": {"platform": platform, "kind": kind,
                         "count": cell["chips"], "memory_peak_bytes": mem}}
    if not trace:
        for mm in metrics_for(bench["end_to_end"], cell["name"]):
            result["metrics"][mm["name"]] = {"value": _e2e(mm["name"], w),
                                             "unit": mm["unit"]}
    else:
        from . import trace as tr

        t = time.perf_counter()
        tdata = tr.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = types.SimpleNamespace(trace=tdata, batches=batches, n=n, d=d,
                                    m=m, k=params.k, peaks=peaks)
        for mm in metrics_for(bench["per_layer"], cell["name"]):
            v = load_reader(mm["name"])(ctx)
            if v is not None:
                result["metrics"][mm["name"]] = {"value": v,
                                                 "unit": mm["unit"]}
        lo_w, hi_w = tdata.window
        result["device"]["busy_s"] = tr.busy_ns(tdata) / 1e9
        result["device"]["window_s"] = (hi_w - lo_w) / 1e9
        result["breakdown"] = {
            "device_ops": [list(x) for x in tr.top_ops(tdata)],
            "idle_gaps": [list(x) for x in tr.idle_gaps(tdata)]}
        log(f"trace read in {time.perf_counter() - t:.3f}s: "
            f"{len(tdata.ops)} device ops, {len(tdata.modules)} program "
            f"runs, {len(tdata.spans)} spans")
    result["workload"] = cell["name"]
    result["seed"] = seed
    result["checks"] = checks
    return result


class NoChip(Exception):
    pass


def report(result: dict) -> None:
    """The compared numbers as the last lines of stderr, then the result
    as the last line of stdout."""
    for name, c in result["checks"].items():
        log(f"check {name} = {c['value']!r} (limit <= {c['limit']!r})")
    log(f"correct = {result['correct']}")
    print(json.dumps(result), flush=True)
