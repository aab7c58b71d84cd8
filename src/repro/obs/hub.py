"""TelemetryHub — the host-side metric registry for the serving stack.

One hub answers "what is the stream doing right now": counters (kernel
dispatch routes, publishes, drift probes), gauges (active m, drift,
trace error — usually mirrored out of an in-graph
``core/telemetry.MetricsState``), latency histograms with the
compile-vs-steady key split that used to be copy-pasted as ``_PhaseTimer``
across ``launch/serve.py``, and two bounded rings: the JSONL event log
(``events``, the last ``EVENT_RING`` publishes, skips and heals) and the
program spans of ``obs.trace.span`` (``spans``, the last ``SPAN_RING``).
``scrape()`` returns the whole registry as a flat dict;
``to_prometheus()`` renders the text exposition format (served by
``obs.export.serve_metrics`` under ``serve.py --metrics-port``).

The hub is plain host state — nothing here ever enters a jitted graph.
Metric identity is ``name`` plus an optional label set, rendered
Prometheus-style (``kernel_dispatch_total{kernel="rbf_gram",route="ref"}``).
"""
from __future__ import annotations

import collections
import contextlib
import re
import threading
import time

from repro.obs.trace import trace_annotation

_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")

# Ring sizes.  A service publishes a few times a second for as long as it
# runs, so an unbounded log would grow for the life of the process:
# 4096 events is over half an hour of publishes at 2/s, and 16384 spans
# hold a few minutes of a service's steps (about six spans each) and
# queries, more than one benchmark window.
EVENT_RING = 4096
SPAN_RING = 16384


def sanitize(name: str) -> str:
    """Prometheus-legal metric name."""
    out = _NAME_RE.sub("_", name)
    return out if not out[:1].isdigit() else "_" + out


def render_key(name: str, labels: dict | None = None) -> str:
    name = sanitize(name)
    if not labels:
        return name
    inner = ",".join(f'{sanitize(str(k))}="{v}"'
                     for k, v in sorted(labels.items()))
    return f"{name}{{{inner}}}"


def _percentiles(samples) -> dict:
    import numpy as np

    arr = np.asarray(samples, float) if len(samples) else np.zeros((1,))
    return {"p50": float(np.percentile(arr, 50)),
            "p90": float(np.percentile(arr, 90)),
            "p99": float(np.percentile(arr, 99)),
            "max": float(arr.max())}


class Counter:
    """Monotone counter handle (hub-registered)."""

    def __init__(self):
        self.value = 0.0

    def inc(self, n=1) -> None:
        self.value += n

    def set(self, v) -> None:
        """Absolute set — for mirroring a cumulative in-graph counter."""
        self.value = float(v)


class Gauge:
    def __init__(self):
        self.value = 0.0

    def set(self, v) -> None:
        self.value = float(v)


class _TimedHandle:
    """Yielded by ``LatencyHistogram.timed``: call ``.sync(x)`` with the
    arrays the phase produced so the recorded wall-clock includes the
    device execution (``jax.block_until_ready``), not just dispatch."""

    def __init__(self):
        self._sync = None

    def sync(self, x) -> None:
        self._sync = x


class LatencyHistogram:
    """Steady-state vs warm-up latency split (one per service phase).

    The first sample of each compilation KEY (bucket rung for updates,
    component count for transforms, ...) pays jit tracing + compile;
    folding it into the same series as steady-state steps is what used
    to pollute the reported p50/p99.  Keyed first calls land in
    ``compile_ms``; everything else in ``ms``.  (The hub-registered
    successor of ``launch/serve.py``'s three ``_PhaseTimer`` copies.)
    """

    def __init__(self, name: str = "phase"):
        self.name = name
        self.ms: list[float] = []
        self.compile_ms: list[float] = []
        self._seen: set = set()

    def add(self, sample_ms: float, key=None) -> None:
        if key not in self._seen:
            self._seen.add(key)
            self.compile_ms.append(sample_ms)
        else:
            self.ms.append(sample_ms)

    @contextlib.contextmanager
    def timed(self, key=None, name: str | None = None):
        """Time a phase (and annotate the profiler timeline with its
        name, so spans line up in Perfetto/TensorBoard).  The yielded
        handle's ``.sync(arrays)`` blocks on device results before the
        clock stops — without it only host dispatch time is measured."""
        handle = _TimedHandle()
        with trace_annotation(name or self.name):
            t0 = time.perf_counter()
            yield handle
            if handle._sync is not None:
                import jax

                jax.block_until_ready(handle._sync)
        self.add((time.perf_counter() - t0) * 1e3, key=key)

    def summary(self, name: str | None = None) -> dict:
        name = name if name is not None else self.name
        pct = _percentiles(self.ms)
        out = {f"{name}_{k}": v for k, v in pct.items()}
        out[f"{name}_compiles"] = len(self.compile_ms)
        out[f"{name}_compile_ms"] = float(sum(self.compile_ms))
        return out


class TelemetryHub:
    """Registry of counters/gauges/histograms plus the event and span
    rings (``collections.deque`` with ``maxlen`` ``EVENT_RING`` and
    ``SPAN_RING``: the oldest entry drops out when a ring is full).

    Thread-safe for the registration paths (the decoupled serving loop
    and a ``--metrics-port`` scrape thread share one hub).
    """

    def __init__(self):
        self._lock = threading.RLock()
        self._counters: dict[str, Counter] = {}
        self._gauges: dict[str, Gauge] = {}
        self._hists: dict[str, LatencyHistogram] = {}
        self.events: collections.deque = collections.deque(maxlen=EVENT_RING)
        self.spans: collections.deque = collections.deque(maxlen=SPAN_RING)
        self._jsonl = None

    # ---- registration ----------------------------------------------------
    def counter(self, name: str, **labels) -> Counter:
        key = render_key(name, labels)
        with self._lock:
            return self._counters.setdefault(key, Counter())

    def gauge(self, name: str, **labels) -> Gauge:
        key = render_key(name, labels)
        with self._lock:
            return self._gauges.setdefault(key, Gauge())

    def histogram(self, name: str) -> LatencyHistogram:
        key = sanitize(name)
        with self._lock:
            return self._hists.setdefault(key, LatencyHistogram(key))

    # convenience spellings
    def inc(self, name: str, n=1, **labels) -> None:
        self.counter(name, **labels).inc(n)

    def set_gauge(self, name: str, v, **labels) -> None:
        self.gauge(name, **labels).set(v)

    # ---- events (JSONL) --------------------------------------------------
    def open_jsonl(self, path) -> None:
        """Stream every subsequent ``emit`` to ``path`` as one JSON line
        (flushed per event — the log survives a crash)."""
        import json  # noqa: F401  (validated import for emit)

        self._jsonl = open(path, "a", buffering=1)

    def emit(self, event: dict) -> None:
        """Append a structured event (a publish, a heal, a scrape...)."""
        import json

        evt = {"ts": time.time(), **event}
        with self._lock:
            self.events.append(evt)
            if self._jsonl is not None:
                self._jsonl.write(json.dumps(evt) + "\n")

    def close_jsonl(self) -> None:
        if self._jsonl is not None:
            self._jsonl.close()
            self._jsonl = None

    # ---- in-graph mirror -------------------------------------------------
    def observe_metrics_state(self, mstate, prefix: str = "stream") -> dict:
        """Mirror a (possibly tenant-stacked) ``core/telemetry.MetricsState``
        into the registry — THE host sync for the in-graph lane.  Scalar
        streams land unlabelled; stacked lanes get a ``tenant`` label per
        entry.  Returns the host-side report dict."""
        import numpy as np

        from repro.core import telemetry as tm

        report = tm.metrics_report(mstate)
        counters = {"ingests", "rejections", "evictions", "downdates",
                    "publishes", "skipped_publishes", "heals_polish",
                    "heals_resync"}
        for field, value in report.items():
            if field.endswith("_total"):
                base = field[: -len("_total")]
                self.counter(f"{prefix}_{base}_total").set(value)
                continue
            kind = "counter" if field in counters else "gauge"
            arr = np.asarray(value)
            if arr.ndim == 0:
                v = float(arr)
                if kind == "counter":
                    self.counter(f"{prefix}_{field}_total").set(v)
                else:
                    self.gauge(f"{prefix}_{field}").set(v)
            else:
                for i, v in enumerate(arr.tolist()):
                    if kind == "counter":
                        self.counter(f"{prefix}_{field}_total",
                                     tenant=i).set(v)
                    else:
                        self.gauge(f"{prefix}_{field}", tenant=i).set(v)
        return report

    # ---- read-out --------------------------------------------------------
    def scrape(self) -> dict:
        """The whole registry as a flat dict: counters/gauges by rendered
        key, histograms expanded through their summaries."""
        with self._lock:
            out: dict = {}
            for key, c in self._counters.items():
                out[key] = c.value
            for key, g in self._gauges.items():
                out[key] = g.value
            for key, h in self._hists.items():
                out.update(h.summary(key))
            return out

    def to_prometheus(self) -> str:
        from repro.obs import export

        return export.to_prometheus(self)

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()
            self.events.clear()
            self.spans.clear()


_DEFAULT = TelemetryHub()


def get_hub() -> TelemetryHub:
    """The process-default hub (kernel dispatch counters land here)."""
    return _DEFAULT


def fresh_hub() -> TelemetryHub:
    """Reset and return the default hub — service entry points call this
    so one process can run several serving mains without cross-talk."""
    _DEFAULT.reset()
    return _DEFAULT


def note_kernel_dispatch(kernel: str, route: str) -> None:
    """Count one kernel *dispatch decision* (pallas / interpret / ref).

    The ``kernels/*/ops.py`` wrappers run at TRACE time inside jit, so
    each increment is one retrace — i.e. a jit-cache MISS (a compile
    event), not a per-step execution.  A steady-state serving loop holds
    these counters flat; growth means recompilation churn (new bucket
    rungs, shape changes) worth investigating.
    """
    _DEFAULT.counter("kernel_dispatch_total", kernel=kernel, route=route
                     ).inc()
