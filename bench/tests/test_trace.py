"""The trace reduction on the CPU, run by hand (outside ``testpaths``):

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests -q

A hand-made trace pins the arithmetic (union of the device operations'
intervals, idle gaps and their labels, per-program device time per
batch, the lead from a search span to its first device operation, the
roofline share); a trace recorded on a TPU v5e (``trace_small.json``,
two batches of each cell's window) pins the names the readers match.
"""

import json
import os
import types

import pytest

from bench import harness
from bench import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))

HAND = tr.Trace(
    ops=[("%fusion.1 = f32[8] fusion(f32[8] %a), kind=kLoop", 120, 150),
         ("%fusion.2 = f32[8] fusion(f32[8] %b), kind=kLoop", 150, 170),
         ('%scan.1 = (s32[32,1,10]) custom-call(f32[1000,64] %c), '
          'custom_call_target="tpu_custom_call"', 180, 195),
         ("%while.3 = (s32[]) while((s32[]) %t), condition=%c, body=%b",
          250, 300),
         ("%fusion.3 = f32[8] fusion(f32[8] %d), kind=kLoop", 250, 290)],
    modules=[("jit_graph(1)", 120, 172), ("jit_scan(2)", 178, 196),
             ("jit_graph(3)", 250, 301)],
    spans=[("bench.search", 100, 200), ("bench.record", 200, 210),
           ("bench.search", 210, 400)])


def ctx(trace, batches, **kw):
    return types.SimpleNamespace(trace=trace, batches=batches, **kw)


def test_union_and_idle_share():
    assert HAND.window == (100, 400)
    assert tr.union_ns([(s, e) for _, s, e in HAND.ops], 100, 400) == 115
    assert tr.union_ns([(0, 10), (5, 20), (30, 40)], 8, 35) == 17
    idle = harness.load_reader("device_idle_share")(ctx(HAND, []))
    assert idle == pytest.approx(1 - 115 / 300)


def test_idle_gaps_are_labelled_by_the_host_span():
    gaps = tr.idle_gaps(HAND)
    assert gaps == [("bench.search", 100e-9), ("bench.search", 55e-9),
                    ("bench.search", 20e-9), ("bench.search", 10e-9)]
    t = tr.Trace(ops=HAND.ops, modules=[], spans=HAND.spans
                 + [("bench.record", 165, 178)])
    assert ("bench.record", 10e-9) in tr.idle_gaps(t)


def test_lead_and_program_time_per_batch():
    assert tr.leads_ns(HAND) == [20, 40]
    lead = harness.load_reader("host_lead_ms")(ctx(HAND, []))
    assert lead == pytest.approx(30 / 1e6)
    graph = harness.load_reader("graph_program_ms")(ctx(HAND, []))
    assert graph == pytest.approx((52 + 51) / 2 / 1e6)
    # the while holds fusion.3: it is left out of the top operations
    assert tr.top_ops(HAND) == [("%fusion.3 kLoop", 40e-9),
                                ("%fusion.1 kLoop", 30e-9),
                                ("%fusion.2 kLoop", 20e-9),
                                ("%scan.1 tpu_custom_call", 15e-9)]


def test_scan_topk_roofline():
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    n, d, m = 1000, 64, 4
    batches = [{"lanes": 32, "scan_lanes": 32},
               {"lanes": 32, "scan_lanes": 0}]
    got = harness.load_reader("scan_topk_roofline")(
        ctx(HAND, batches, n=n, d=d, m=m, peaks=peaks))
    least = max(n * (d + m) * 4 / 819e9, 2 * 32 * n * d / 197e12)
    assert got == pytest.approx(100 * least / 15e-9)
    # no kernel in the window: nothing to read, and never a 0
    none = harness.load_reader("scan_topk_roofline")(
        ctx(tr.Trace(ops=HAND.ops[:2], modules=[], spans=HAND.spans),
            batches, n=n, d=d, m=m, peaks=peaks))
    assert none is None


with open(os.path.join(HERE, "trace_small.json")) as f:
    RECORDED = json.load(f)


@pytest.mark.parametrize("cell", ["youtube-1024.graph", "youtube-1024.scan"])
def test_recorded_trace(cell):
    rec = RECORDED[cell]
    t = tr.Trace.from_json(rec)
    peaks = harness.load_peaks("TPU v5 lite")
    c = ctx(t, rec["batches"], n=rec["n"], d=rec["d"], m=rec["m"],
            k=10, peaks=peaks)
    for name, want in rec["expected"].items():
        got = harness.load_reader(name)(c)
        assert got == (pytest.approx(want) if want is not None else None), \
            name
    lo, hi = t.window
    assert 0 < tr.busy_ns(t) <= hi - lo
    if cell.endswith(".graph"):
        # the graph program is most of the batch; the host lead the rest
        assert rec["expected"]["graph_program_ms"] > 3000
        assert 0 < rec["expected"]["device_idle_share"] < 0.05
    else:
        # the scan kernel ran, and its share of the roofline is a share
        assert 0 < rec["expected"]["scan_topk_roofline"] <= 100
        assert rec["expected"]["device_idle_share"] > 0.9
