"""Host-side observability: hub registry, phase tracing, exporters.

The in-graph half lives in ``repro.core.telemetry`` (a ``MetricsState``
pytree riding the stream); this package is everything that happens on
the host — the ``TelemetryHub`` registry, the program spans of
``span()`` (kept on the hub's span ring and annotated on the profiler's
timeline) with the JAX traces and compiles booked to them, per-kernel
dispatch counters, and the Prometheus / JSONL export surface used by
``launch/serve.py``.
"""
from repro.obs.export import (parse_prometheus, read_jsonl, serve_metrics,
                              to_prometheus, write_jsonl)
from repro.obs.hub import (Counter, Gauge, LatencyHistogram, TelemetryHub,
                           fresh_hub, get_hub, note_kernel_dispatch,
                           render_key, sanitize)
from repro.obs.trace import span, trace_annotation

__all__ = [
    "Counter", "Gauge", "LatencyHistogram", "TelemetryHub",
    "fresh_hub", "get_hub", "note_kernel_dispatch", "render_key",
    "sanitize", "span", "trace_annotation", "to_prometheus",
    "parse_prometheus", "serve_metrics", "write_jsonl", "read_jsonl",
]
