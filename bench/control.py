#!/usr/bin/env python3
"""The control of ``correct``, at a cell's own size, on several seeds,
in one process. Run by hand on the chip:

    python3 bench/control.py --workload youtube-1024.graph --queries 136 \
        --seeds 11 12 13

It puts the plain reference in the program's place in bfloat16, the
precision below the configuration's float32: for each seed it makes the
run's corpus and query pool, answers the first ``--queries`` window
queries with the bfloat16 scan and holds them to a run's checks. It
prints one JSON line of the compared numbers per seed; the control has
to come out not correct.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(workload: str, seed: int, n_queries: int,
             overrides: dict = None) -> dict:
    from bench import gen, harness, reference

    _, _, cfg, traffic = harness.load_cell(workload)
    cfg = {**cfg, **(overrides or {})}
    vecs, attrs = gen.make_corpus(cfg, seed)
    n_warm = int(traffic["batch"]) * int(traffic["warm_batches"])
    Q, lo, hi, _ = gen.make_pool(vecs, attrs, traffic,
                                 n_warm + int(traffic["pool"]), seed,
                                 cfg["query_noise"])
    sl = slice(n_warm, n_warm + n_queries)
    Q, lo, hi = Q[sl], lo[sl], hi[sl]
    k = cfg["search"]["k"]
    ref_ids, ref_d, cnt = reference.exact(vecs, attrs, Q, lo, hi, k)
    ids, dists = reference.answer_lowp(vecs, attrs, Q, lo, hi, k)
    # the control is a scan: every lane of it is a scanned lane
    checks, lane_fail, rec, _ = reference.judge(
        ids, dists, vecs, attrs, Q, lo, hi, ref_ids, ref_d, cnt, k,
        exact=bool(traffic.get("exact")), batches=[(n_queries, n_queries)])
    return {"workload": workload, "seed": seed, "queries": n_queries,
            "correct": reference.passed(checks),
            "failed": int(lane_fail.sum()), "checks": checks}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--queries", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    import jax

    print(f"device {jax.devices()[0].device_kind}", file=sys.stderr)
    for seed in args.seeds:
        t = time.perf_counter()
        r = readings(args.workload, seed, args.queries)
        r["seconds"] = time.perf_counter() - t
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
