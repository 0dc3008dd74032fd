"""Helpers the per-layer metric readers (``bench/metrics/*.py``) share.

A reader is ``read(ctx, trace) -> float | None``: ``ctx`` is what the
cell recorded about its window (host-clock stamps, counts, the
configuration's model sizes, the chip's peaks), ``trace`` the reduced
profiler trace (:class:`bench.trace_reduce.TraceSummary`).  A reader that
finds nothing to read returns None and the metric is left out of the
result line; a share of a roofline or a peak is never reported as 0 for
want of a reading.
"""
from __future__ import annotations

import math


def pct(values, q):
    """Nearest-rank q-th percentile (0-100); None for no values."""
    v = sorted(values)
    if not v:
        return None
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def span(ctx):
    """Host-clock bounds of the traced part of the window, else of the
    whole window."""
    if ctx.get("trace_t0") is not None:
        return ctx["trace_t0"], ctx["trace_t1"]
    return ctx["t0"], ctx["t_close"]


def in_window(ctx, items):
    """Entries ``(t, ...)`` stamped inside the traced window."""
    a, b = span(ctx)
    return [x for x in items if a <= x[0] <= b]


def share(work_s, device_s):
    """Per cent of ``device_s`` that the least possible time ``work_s``
    is; None when there is no device time to divide by."""
    if not device_s or work_s is None:
        return None
    return 100.0 * work_s / device_s
