"""99th percentile of how late the load generator submitted each request
against its due time: a starved generator must not read as a fast
server."""
from bench.readers import pct


def read(ctx, trace):
    v = pct(ctx["submit_lags"], 99)
    return None if v is None else 1e3 * v
