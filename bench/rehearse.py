#!/usr/bin/env python3
"""CPU rehearsal of a benchmark run at a tiny size, run by hand:

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--workload W] [--trace 1]

Drives the whole of ``bench/run.py``'s path (corpus, pool, device build,
``KHIService``, warm-up, the window, the reference and the check) on the
CPU with the Pallas kernels in interpret mode and the configuration cut
to ``--n`` rows of width ``--d``, skipping only the look for a chip. Then
runs ``bench/run.py`` itself, which has to stop at that look: exit 2 and
no result line. Its numbers are a CPU's and name no device metric.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="youtube-1024.graph")
    ap.add_argument("--seed", type=int, default=2**31 + 12345)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--d", type=int, default=32)
    args = ap.parse_args()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, ROOT)
    from bench import harness

    result = harness.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START,
                         require_tpu=False, interpret=True,
                         overrides={"n": args.n, "d": args.d,
                                    "latent_dim": min(8, args.d)})
    harness.report(result)
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"),
                        "--workload", args.workload, "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, cwd=ROOT,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    print(f"bench/run.py on the CPU: exit {p.returncode}, "
          f"{len(p.stdout)} bytes of stdout, stderr: {p.stderr.strip()}",
          file=sys.stderr)
    return 0 if p.returncode != 0 and not p.stdout.strip() else 1


if __name__ == "__main__":
    sys.exit(main())
