"""The comparison that decides ``correct`` has to fail what it exists to
catch. Run by hand on the CPU (outside the repo's ``testpaths``):

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests -q

* the control comes out not correct: the reference in bfloat16 in the
  program's place;
* a run at a tiny size, with the look for a chip skipped and the Pallas
  kernels interpreted, comes out correct as it is, and not correct with
  the timed ``search`` broken underneath in each way a cell can be:
  an answer returned stale (the state left unchanged), half of each
  batch left out, one answer altered where it is produced;
* in the scan cell, a small box answered approximately comes out not
  correct where the service counts its lane as scanned, and correct
  where the service counts it as sent to the graph path, as the
  planner's routing bound may.
"""

import time

import numpy as np
import pytest

from bench import control, gen, harness

TINY = {"n": 4096, "d": 32, "latent_dim": 8}
CELLS = ("youtube-1024.graph", "youtube-1024.scan")


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    r = control.readings(workload, seed=2**31 + 7, n_queries=64,
                         overrides=TINY)
    assert not r["correct"], r["checks"]


def stale(search):
    """Each call answers with the previous call's answer."""
    last = []

    def f(q, lo, hi):
        out = search(q, lo, hi)
        last.append(out)
        return last[-2] if len(last) > 1 else out
    return f


def half_left_out(search):
    """The second half of every batch comes back empty."""
    def f(q, lo, hi):
        ids, d = search(q, lo, hi)
        ids, d = ids.copy(), d.copy()
        h = ids.shape[0] // 2
        ids[h:], d[h:] = -1, np.inf
        return ids, d
    return f


def altered(search):
    """The first id of each batch's first answer is another row's, with
    its distance left as it was."""
    def f(q, lo, hi):
        ids, d = search(q, lo, hi)
        ids = ids.copy()
        ids[0, 0] = (ids[0, 0] + 1) % TINY["n"]
        return ids, d
    return f


def missed_neighbour(workload, counted_scanned: bool):
    """Lane 0 of each batch answered approximately: its answer misses the
    10th nearest in-box row for the 11th, with true distances. Where not
    ``counted_scanned``, the service's count of scanned lanes drops by
    one, as when the planner sends the lane to the graph path."""
    _, _, cfg, _ = harness.load_cell(workload)
    vecs, attrs = gen.make_corpus({**cfg, **TINY}, SEED)

    def wrap(search):
        svc = search.__self__

        def f(q, lo, hi):
            ids, d = search(q, lo, hi)
            ids, d = ids.copy(), d.copy()
            rows = np.nonzero(np.all((attrs >= lo[0]) & (attrs <= hi[0]),
                                     axis=1))[0]
            dd = np.sum((vecs[rows] - q[0]) ** 2, axis=1)
            k = ids.shape[1]
            assert len(rows) > k
            pick = np.argsort(dd, kind="stable")[list(range(k - 1)) + [k]]
            ids[0], d[0] = rows[pick], dd[pick]
            if not counted_scanned:
                svc.stats["scan_lanes"] -= 1
            return ids, d
        return f
    return wrap


SEED = 2**31 + 3


def _run(workload, fault):
    return harness.run(workload, SEED, 1.0, False,
                       t_start=time.perf_counter(), require_tpu=False,
                       interpret=True, overrides=TINY, fault=fault)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", [None, stale, half_left_out, altered],
                         ids=["sound", "stale", "half_left_out", "altered"])
def test_fault_is_caught(workload, fault):
    r = _run(workload, fault)
    assert r["correct"] == (fault is None), r["checks"]


def test_approximate_scan_lane_is_caught():
    workload = "youtube-1024.scan"
    r = _run(workload, missed_neighbour(workload, counted_scanned=True))
    assert not r["correct"], r["checks"]
    c = r["checks"]["scan_inexact"]
    assert c["value"] > c["limit"], c


def test_graph_routed_lane_reads_correct():
    workload = "youtube-1024.scan"
    r = _run(workload, missed_neighbour(workload, counted_scanned=False))
    assert r["correct"], r["checks"]
