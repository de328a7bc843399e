"""host_lead_ms: per batch, the time from the benchmark's span around the
``KHIService.search`` call to the first device operation of that batch,
averaged over the traced window. It holds the host's plan pass
(``Planner.plan``), padding, the result-cache hashing and the copy of
the batch to the device: the service and planner layers, on the host."""

from bench import trace as tr


def read(ctx):
    leads = tr.leads_ns(ctx.trace)
    return sum(leads) / len(leads) / 1e6 if leads else None
