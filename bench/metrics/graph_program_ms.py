"""graph_program_ms: per batch, the device time of the planner's graph
program (Phase-A level router and Phase-B hop loop, jitted as ``graph``),
summed over its runs on the device's program line and averaged over the
batches that ran it."""

from bench import trace as tr

# the program's runs as the device's "XLA Modules" line names them
PROGRAMS = ("jit_graph",)


def read(ctx):
    per = tr.time_per_span_ns(ctx.trace.modules, PROGRAMS, ctx.trace)
    return sum(per) / len(per) / 1e6 if per else None
