"""90th percentile, over the requests due in the window, of the time from
when a request was due to its admission into a slot (the engine's
``ServeMetrics`` admission stamp)."""
from bench.readers import pct


def read(ctx, trace):
    v = pct(ctx["queue_waits"], 90)
    return None if v is None else 1e3 * v
