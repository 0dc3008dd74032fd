"""``repro.obs`` — dependency-free tracing, metrics, and numeric health.

Three stdlib-only layers threaded through serve, kernels, and train:

* :mod:`repro.obs.trace` — :class:`Tracer` span/instant/counter events →
  Chrome-trace/Perfetto JSON (``launch.serve --trace-out``), and
  :class:`span`, the step-phase span that also lands in a
  ``jax.profiler`` trace as ``repro:<name>``;
* :mod:`repro.obs.metrics` — :class:`MetricsRegistry` of counters,
  gauges, and log-bucketed histograms, with JSONL snapshots and a
  Prometheus-text endpoint (``--metrics-port``);
* :mod:`repro.obs.numerics` — the §5 controller's exponent/overflow
  timeline as JSONL (``--numerics-log``), serve- and train-side.

Every hook in the stack is zero-cost when disabled: call sites hold
``None`` and guard with one attribute check — no device syncs, no extra
per-token host work, token streams bit-identical with obs off.
"""
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      start_http_server)
from .numerics import (NumericsLog, count_moves, read_jsonl, serve_records,
                       train_records)
from .trace import Tracer, span, validate_trace

__all__ = [
    "Tracer", "span", "validate_trace",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "start_http_server",
    "NumericsLog", "serve_records", "train_records", "count_moves",
    "read_jsonl",
]
