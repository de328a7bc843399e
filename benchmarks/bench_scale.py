"""Shard-scaling sweep for the collective query pipeline (DESIGN.md §14),
emulated on the host CPU: every timing it prints or saves is a CPU
emulation of S devices, labelled so, and no chip measurement. It refuses
to run where the parent's JAX platform is a TPU (its children would force
the CPU and report those timings under the name of shard scaling); the
four-chip check on a TPU host is ``chip_smoke.py --chips 4``.

Each point S in {1, 2, 4, 8} runs in a fresh subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=S`` (device count must
be fixed before jax imports): build the corpus round-robin into S shards,
lower ``make_sharded_search_fn`` on a (1, S) (data, model) mesh, assert the
collective answers bit-identical to ``search_sharded_emulated``, and time
the steady state for every merge form S admits (halving needs S a power of
two >= 2).

QPS accounting — this box is 1 CPU core, so S emulated devices serialize:
wall-clock *degrades* mildly with S (each device still runs its whole
local program; the merge is the only part that shrinks). The sweep
therefore reports both

  * ``qps_wall``    = B / t_wall — what this host actually served;
  * ``qps_scaled``  = B·S / t_wall — per-device busy-time throughput: with
    S programs serialized on one core, t_wall/S approximates one device's
    busy time, so B·S/t_wall is the batch rate of S devices running
    concurrently (what the same program does when every mesh slot is real
    hardware). On a host with >= S cores the two converge and ``qps_wall``
    is authoritative.

``host_parallelism`` records the core count so readers (and the CI gate)
know which column is load-bearing: the scaling gate checks
``qps_scaled(S=4)/qps_scaled(S=1)`` when cores < S and the wall ratio
otherwise. Merge traffic is reported analytically per device per query
(``merge_bytes_per_device``): the halving form moves 12k·log2(S) bytes vs
the all_gather's 8k·(S-1).
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent

SCALE_CFG = {
    # corpus, batch, timing iters per point
    "smoke": dict(n=2048, d=16, m=2, B=64, iters=3),
    "small": dict(n=8192, d=24, m=2, B=128, iters=5),
    "paper": dict(n=16384, d=32, m=3, B=256, iters=8),
}
S_SWEEP = (1, 2, 4, 8)
K = 10


def _child(s_shards: int, scale: str) -> dict:
    """Runs inside the subprocess: one sweep point."""
    import numpy as np
    import jax

    from repro.core.engine import SearchParams
    from repro.core.khi import KHIConfig
    from repro.core.sharded import (build_sharded, make_sharded_search_fn,
                                    merge_bytes_per_device,
                                    search_sharded_emulated)
    from repro.data import DatasetSpec, make_dataset, make_queries
    from repro.launch.mesh import make_query_mesh

    cfg = SCALE_CFG[scale]
    assert len(jax.devices()) >= s_shards, "XLA_FLAGS not honored"
    vecs, attrs = make_dataset(DatasetSpec(
        "scalebench", n=cfg["n"], d=cfg["d"], m=cfg["m"], seed=0))
    t0 = time.perf_counter()
    skhi = build_sharded(vecs, attrs, s_shards,
                         KHIConfig(M=16, builder="bulk"))
    build_s = time.perf_counter() - t0
    Q, preds = make_queries(vecs, attrs, n_queries=cfg["B"], sigma=1 / 4,
                            seed=3)
    qlo = np.stack([p.lo for p in preds]).astype(np.float32)
    qhi = np.stack([p.hi for p in preds]).astype(np.float32)
    # mix wide (graph) and narrow (scan) lanes so auto dispatch branches
    qlo[: cfg["B"] // 3] = attrs.min(0) - 1
    qhi[: cfg["B"] // 3] = attrs.max(0) + 1
    p = SearchParams(k=K, ef=48, c_n=16, strategy="auto")
    mesh = make_query_mesh(s_shards, 1)

    ei, ed, _ = search_sharded_emulated(skhi, Q, qlo, qhi, p)
    pow2 = s_shards >= 2 and (s_shards & (s_shards - 1)) == 0
    merges = ("halving", "allgather") if pow2 else ("allgather",)
    out = {"S": s_shards, "platform": jax.devices()[0].platform,
           "devices": len(jax.devices()), "build_s": round(build_s, 2),
           "merges": {}}
    for merge in merges:
        fn = make_sharded_search_fn(p, mesh, skhi=skhi,
                                    on_undersized="adjust", merge=merge)
        ci, cd = jax.device_get(fn(skhi, Q, qlo, qhi))   # compile + warm
        ids_equal = bool(np.array_equal(ci, np.asarray(ei))
                         and np.array_equal(cd, np.asarray(ed)))
        best = float("inf")
        for _ in range(cfg["iters"]):
            t0 = time.perf_counter()
            r = fn(skhi, Q, qlo, qhi)
            jax.block_until_ready(r)
            best = min(best, time.perf_counter() - t0)
        out["merges"][merge] = {
            "t_wall_ms": round(best * 1e3, 3),
            "qps_wall": round(cfg["B"] / best, 1),
            "qps_scaled": round(cfg["B"] * s_shards / best, 1),
            "merge_bytes_per_device": merge_bytes_per_device(
                K, s_shards, merge),
            "ids_equal_emulated": ids_equal,
        }
    return out


def _spawn(s_shards: int, scale: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={s_shards}")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO / "src"), str(REPO)] +
        ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run(
        [sys.executable, "-u", "-m", "benchmarks.bench_scale",
         "--child", str(s_shards), "--scale", scale],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"S={s_shards} child failed:\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _best(point: dict) -> dict:
    """The point's headline merge: halving when available."""
    return point["merges"].get("halving") or point["merges"]["allgather"]


def refuse_on_tpu() -> None:
    """Stop before spawning children when this process runs on a TPU."""
    import jax

    if jax.default_backend() == "tpu":
        raise SystemExit(
            "bench_scale emulates S devices on the CPU and measures no chip; "
            "on a TPU host run `python chip_smoke.py --chips 4`")


def run(scale: str = "smoke", sweep=S_SWEEP, gate: float | None = None):
    refuse_on_tpu()
    cfg = SCALE_CFG[scale]
    cores = os.cpu_count() or 1
    rows = [_spawn(s, scale) for s in sweep]
    for r in rows:
        assert r["platform"] == "cpu", \
            f"S={r['S']} child ran on {r['platform']}, not the CPU"
        for m, v in r["merges"].items():
            assert v["ids_equal_emulated"], \
                f"S={r['S']} merge={m}: collective != emulated"
    base = _best(rows[0])
    for r in rows:
        b = _best(r)
        col = "qps_scaled" if cores < r["S"] else "qps_wall"
        b["speedup_vs_S1"] = round(b[col] / base[col], 2)
    payload = {
        "platform": "cpu-emulated", "scale": scale, "k": K,
        "host_parallelism": cores,
        "ratio_column": "qps_scaled (cores < S; see module docstring)"
                        if cores < max(sweep) else "qps_wall",
        "dataset": {k: cfg[k] for k in ("n", "d", "m", "B")},
        "rows": rows,
    }
    if gate is not None:
        r4 = next(r for r in rows if r["S"] == 4)
        ratio = _best(r4)["speedup_vs_S1"]
        assert ratio >= gate, (
            f"scaling gate: QPS(S=4)/QPS(S=1) = {ratio} < {gate}")
        payload["gate"] = {"min_ratio": gate, "measured": ratio}
    from .common import save_results
    save_results("scale", payload)
    return payload


def csv_lines(payload):
    out = []
    for r in payload["rows"]:
        for m, v in r["merges"].items():
            out.append(f"scale_cpu_emulated_S{r['S']}_{m},"
                       f"{v['t_wall_ms'] * 1e3:.0f},"
                       f"platform={r['platform']}x{r['devices']};"
                       f"qps_wall={v['qps_wall']};"
                       f"qps_scaled={v['qps_scaled']};"
                       f"bytes={v['merge_bytes_per_device']}")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", type=int, default=None)
    ap.add_argument("--scale", default="smoke", choices=list(SCALE_CFG))
    ap.add_argument("--ci", action="store_true",
                    help="S in {1,4} only, gate the S=4/S=1 ratio at 2.0")
    args = ap.parse_args(argv)
    if args.child is not None:
        print(json.dumps(_child(args.child, args.scale)))
        return
    sweep = (1, 4) if args.ci else S_SWEEP
    payload = run(args.scale, sweep=sweep, gate=2.0 if args.ci else None)
    print("\n".join(csv_lines(payload)))


if __name__ == "__main__":
    main()
