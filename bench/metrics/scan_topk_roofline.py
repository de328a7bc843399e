"""scan_topk_roofline: the ``scan_topk`` kernel's device time against the
least time the chip needs for the work that an exact filtered scan of
the batch requires, whatever implements it: one read of the corpus and
its attributes, n * (d + m) * 4 bytes per batch, and 2 * B * n * d
operations for B scan lanes. The least time is the larger of bytes over
the HBM bandwidth and operations over the bf16 peak; the share is that
time, summed over the batches, over the kernel's time summed over the
same batches, in percent."""

import bisect

from bench import trace as tr

# the kernel: the Pallas custom call ("tpu_custom_call" on the device's
# "XLA Ops" line) inside a run of the planner's scan program ("jit_scan"
# on its "XLA Modules" line)
PROGRAMS = ("jit_scan",)
KERNELS = ("tpu_custom_call",)


def work(n: int, d: int, m: int, lanes: int):
    """(bytes, operations) an exact filtered scan of ``lanes`` queries
    over n rows needs at the least."""
    return n * (d + m) * 4, 2 * lanes * n * d


def read(ctx):
    if ctx.peaks is None:
        return None
    kern = [op for op in tr.inside(ctx.trace.ops, ctx.trace.modules,
                                   PROGRAMS) if tr.matches(op[0], KERNELS)]
    ops = sorted((s, e) for _, s, e in kern)
    starts = [s for s, _ in ops]
    spans = ctx.trace.search_spans()
    if len(spans) != len(ctx.batches):
        return None
    least = kernel = 0.0
    for b, (_, s, e) in zip(ctx.batches, spans):
        i, j = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
        if j == i or not b["scan_lanes"]:
            continue
        nbytes, nops = work(ctx.n, ctx.d, ctx.m, b["scan_lanes"])
        least += max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                     nops / ctx.peaks["bf16_flops_per_s"])
        kernel += sum(y - x for x, y in ops[i:j]) / 1e9
    return 100.0 * least / kernel if kernel else None
