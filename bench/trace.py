"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is reduced to three lists of ``(name, start_ns, end_ns)`` on one
clock: the device's operations (the ``XLA Ops`` line of each device
plane), the device's program runs (its ``XLA Modules`` line) and the
benchmark's own host spans (``TraceAnnotation`` names starting with
``bench.``). Everything below works on those lists, so the CPU test can
drive it from a small recorded trace (``bench/tests/trace_small.json``).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench."
SEARCH_SPAN = "bench.search"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    ops: list          # [(name, start_ns, end_ns)] device operations
    modules: list      # [(name, start_ns, end_ns)] device program runs
    spans: list        # [(name, start_ns, end_ns)] benchmark host spans
    n_devices: int = 1

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(ops=[tuple(e) for e in d["ops"]],
                   modules=[tuple(e) for e in d["modules"]],
                   spans=[tuple(e) for e in d["spans"]],
                   n_devices=d.get("n_devices", 1))

    @property
    def window(self) -> tuple:
        """The traced window: the first search span's start to the last
        one's end."""
        s = [e for e in self.spans if e[0] == SEARCH_SPAN]
        return (min(e[1] for e in s), max(e[2] for e in s)) if s else (0, 0)

    def search_spans(self) -> list:
        return sorted((e for e in self.spans if e[0] == SEARCH_SPAN),
                      key=lambda e: e[1])


def load(trace_dir: str) -> Trace:
    """Read the ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    ops, modules, spans, n_dev = [], [], [], 0
    for path in paths:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            device = plane.name.startswith("/device:") and \
                "CPU" not in plane.name
            if device:
                lines = {ln.name: ln for ln in plane.lines}
                if OPS_LINE not in lines:
                    continue
                n_dev += 1
                ops += [(e.name, e.start_ns, e.end_ns)
                        for e in lines[OPS_LINE].events]
                if MODULES_LINE in lines:
                    modules += [(e.name, e.start_ns, e.end_ns)
                                for e in lines[MODULES_LINE].events]
            elif plane.name.startswith("/host:"):
                for ln in plane.lines:
                    spans += [(e.name, e.start_ns, e.end_ns)
                              for e in ln.events
                              if e.name.startswith(SPAN_PREFIX)]
    return Trace(ops=ops, modules=modules, spans=spans,
                 n_devices=max(n_dev, 1))


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(trace: Trace) -> float:
    """Time in the traced window in which an operation ran on the device,
    averaged over the devices."""
    lo, hi = trace.window
    return union_ns([(s, e) for _, s, e in trace.ops], lo, hi) \
        / trace.n_devices


def idle_gaps(trace: Trace, top: int = 10) -> list:
    """The longest stretches of the traced window with no device
    operation, each named by the benchmark span the host was in at its
    start ("host" where it was in none): [(name, seconds)]."""
    lo, hi = trace.window
    ivs = sorted((max(s, lo), min(e, hi)) for _, s, e in trace.ops
                 if e > lo and s < hi)
    gaps, t = [], lo
    for s, e in ivs:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    spans = sorted(trace.spans, key=lambda e: e[2] - e[1])   # innermost

    def label(t0):
        for name, s, e in spans:
            if s <= t0 < e:
                return name
        return "host"

    gaps.sort(key=lambda g: g[0] - g[1])
    return [(label(s), (e - s) / 1e9) for s, e in gaps[:top]]


def short_name(op: str) -> str:
    """An operation's HLO instruction name and kind, without its operand
    list: "%fusion.274 kCustom", "%scan.1 tpu_custom_call"."""
    head = op.split(" = ", 1)[0]
    m = (re.search(r"custom_call_target=\"(\w+)\"", op)
         or re.search(r"kind=(k\w+)", op)
         or re.search(r"\s([a-z][\w-]*)\(", op.split(" = ", 1)[-1]))
    return f"{head} {m.group(1)}" if m else head


def leaf_ops(trace: Trace) -> list:
    """The operations that hold no other: a ``while`` or ``conditional``
    is an event of its own on the ops line, around the events of its
    body."""
    ops = sorted(trace.ops, key=lambda e: (e[1], -e[2]))
    return [op for op, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= op[2]]


def top_ops(trace: Trace, top: int = 10) -> list:
    """The device operations that took the most time in the window, by
    short name, leaving out those that hold others: [(name, seconds)]."""
    lo, hi = trace.window
    tot: dict = {}
    for name, s, e in leaf_ops(trace):
        d = min(e, hi) - max(s, lo)
        if d > 0:
            key = short_name(name)
            tot[key] = tot.get(key, 0.0) + d
    return [(n, t / 1e9) for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def leads_ns(trace: Trace) -> list:
    """Per search span that drove the device: the time from the span's
    start to the first device operation inside it."""
    starts = sorted(s for _, s, _ in trace.ops)
    out = []
    for _, s, e in trace.search_spans():
        i = bisect.bisect_left(starts, s)
        if i < len(starts) and starts[i] < e:
            out.append(starts[i] - s)
    return out


def matches(name: str, patterns) -> bool:
    return any(p in name for p in patterns)


def inside(ops, modules, patterns) -> list:
    """The ``ops`` that run inside a program run whose name holds one of
    ``patterns``."""
    runs = sorted((s, e) for name, s, e in modules if matches(name, patterns))
    starts = [s for s, _ in runs]
    out = []
    for op in ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[2] <= runs[i][1]:
            out.append(op)
    return out


def time_per_span_ns(events, patterns, trace: Trace) -> list:
    """Per search span: the summed duration of the events whose name
    holds one of ``patterns`` and that start inside the span. Spans with
    none are left out."""
    ev = sorted((s, e) for name, s, e in events if matches(name, patterns))
    starts = [s for s, _ in ev]
    out = []
    for _, s, e in trace.search_spans():
        i, j = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
        if j > i:
            out.append(sum(b - a for a, b in ev[i:j]))
    return out
