"""device_idle_share: 1 - (union of the device operations' intervals /
the traced window), the window running from the first search span's
start to the last one's end."""

from bench import trace as tr


def read(ctx):
    lo, hi = ctx.trace.window
    if hi <= lo:
        return None
    return 1.0 - tr.busy_ns(ctx.trace) / (hi - lo)
