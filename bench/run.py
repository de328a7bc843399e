#!/usr/bin/env python3
"""Benchmark of filtered vector search (KHI) on one TPU.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` from the root of a checkout: makes
the corpus and the query pool from ``--seed``, builds the index on the
device, brings up ``KHIService`` with the configuration's search
parameters, warms the cell's shapes, drives ``KHIService.search`` in a
closed loop for ``--seconds``, checks every answer of the window against
the plain reference, and prints one JSON line as the last line of
standard output. With ``--trace 1`` the window is traced and the line
holds the per-layer metrics; with ``--trace 0`` the end-to-end ones.

Exits 2 and prints no result where JAX finds no TPU, or fewer chips than
the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    # the persistent compile cache lives at a fixed path in the checkout;
    # the TPU runtime writes no log files outside it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench import harness

    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
