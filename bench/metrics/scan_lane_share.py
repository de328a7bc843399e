"""scan_lane_share: the lanes of the window that the planner sent to the
exact scan program, over all lanes served (``KHIService.stats
["scan_lanes"]``, read at each batch's end)."""


def read(ctx):
    lanes = sum(b["lanes"] for b in ctx.batches)
    return sum(b["scan_lanes"] for b in ctx.batches) / lanes if lanes else None
