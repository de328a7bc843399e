"""Benchmark harness entry: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (plus saves JSON under experiments/).

    PYTHONPATH=src python -m benchmarks.run [--scale smoke|small|paper]
"""

from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="smoke",
                    choices=["smoke", "small", "paper"])
    ap.add_argument("--only", default=None,
                    help="comma list: qps_recall,qps_smoke,convergence,"
                         "vary_k,vary_card,build,build_bench,kernels,serve,"
                         "selectivity,ingest,load,scale")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None

    from . import build_and_size, build_bench, convergence, ingest_bench
    from . import kernels_bench, load_bench, qps_recall, qps_smoke
    from . import selectivity_bench, serve_bench, vary_card, vary_k

    lines = ["name,us_per_call,derived"]
    t0 = time.time()

    def want(name):
        return only is None or name in only

    if want("qps_recall"):
        lines += qps_recall.csv_lines(qps_recall.run(args.scale))
    if want("qps_smoke"):
        lines += qps_smoke.csv_lines(qps_smoke.run(args.scale))
    if want("convergence"):
        lines += convergence.csv_lines(convergence.run(args.scale))
    if want("vary_k"):
        lines += vary_k.csv_lines(vary_k.run(args.scale))
    if want("vary_card"):
        lines += vary_card.csv_lines(vary_card.run(args.scale))
    if want("build"):
        lines += build_and_size.csv_lines(build_and_size.run(args.scale))
    if want("build_bench"):
        lines += build_bench.csv_lines(build_bench.run(args.scale))
    if want("kernels"):
        lines += kernels_bench.csv_lines(kernels_bench.run(args.scale))
    if want("serve"):
        lines += serve_bench.csv_lines(serve_bench.run(args.scale))
    if want("selectivity"):
        lines += selectivity_bench.csv_lines(selectivity_bench.run(args.scale))
    if want("ingest"):
        lines += ingest_bench.csv_lines(ingest_bench.run(args.scale))
    if want("load"):
        lines += load_bench.csv_lines(load_bench.run(args.scale))
    if want("scale"):
        # CPU emulation of S devices; refuses on a TPU (bench_scale docs)
        from . import bench_scale
        lines += bench_scale.csv_lines(bench_scale.run(args.scale))

    print(f"\n# benchmarks done in {time.time()-t0:.0f}s "
          f"(scale={args.scale})")
    print("\n".join(lines))


if __name__ == "__main__":
    main()
