"""Program spans — named host spans at the service's layer boundaries.

``span("kpca.publish")`` marks one call into a layer.  While it is open
it sits on a per-thread stack, so spans opened inside it are its
children: they take its name as their ``parent`` and inherit its ids
(the ingest step number, the query sequence number) and its hub.  When
it closes it records, on the hub's bounded span ring
(``TelemetryHub.spans``):

    {"name", "start_ns", "end_ns", "parent", <ids>,
     "traces", "trace_s", "compiles"}

with both times from ``time.time_ns()`` — the clock of the profiler's
host events, whose trace offsets count from the ``profile_start_time``
of the trace's ``Task Environment`` plane — and, in the last three, the
JAX traces, trace seconds and lowerings/compiles that happened while it
was the innermost open span.  Each span also opens
``jax.profiler.TraceAnnotation(name, **ids)``, so under a profiler
capture it is a slice on the host timeline with its ids as event
statistics; without a capture the annotation costs next to nothing.

One ``jax.monitoring`` duration listener, registered when this module
is imported, books every JAX trace and compile to the innermost open
span of the thread that caused it — ``span="none"`` when none is open —
in the hub counters ``jax_traces_total{span}``,
``jax_trace_seconds_total{span}`` and ``jax_compiles_total{span}``
(a new program counts one lowering and one backend compile).  A span's
bookings reach the hub when it closes, so an event costs the listener
a few attribute updates.  An eager
``jax.vmap`` re-traces its operations on every call and books traces
here; a warm jitted call books none.
"""
from __future__ import annotations

import contextlib
import threading
import time

import jax
from jax.profiler import TraceAnnotation as trace_annotation

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

_local = threading.local()


class _Open:
    """An open span: what its children inherit, what JAX booked to it."""

    __slots__ = ("name", "ids", "hub", "traces", "trace_s", "compiles")

    def __init__(self, name: str, ids: dict, hub):
        self.name, self.ids, self.hub = name, ids, hub
        self.traces = 0
        self.trace_s = 0.0
        self.compiles = 0


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def current() -> _Open | None:
    """The innermost span open on this thread (None outside any)."""
    st = _stack()
    return st[-1] if st else None


@contextlib.contextmanager
def span(name: str, *, hub=None, **ids):
    """Record one call into a layer (see the module docstring).

    ``hub`` defaults to the enclosing span's hub, else the process hub;
    ``ids`` are merged over the enclosing span's."""
    st = _stack()
    parent = st[-1] if st else None
    if parent is not None:
        ids = {**parent.ids, **ids}
        hub = hub if hub is not None else parent.hub
    if hub is None:
        from repro.obs.hub import get_hub

        hub = get_hub()
    frame = _Open(name, ids, hub)
    st.append(frame)
    start = None
    try:
        with trace_annotation(name, **ids):
            start = time.time_ns()
            try:
                yield
            finally:
                end = time.time_ns()
    finally:
        st.pop()
        _book(hub, name, frame.traces, frame.trace_s, frame.compiles)
        if start is not None:
            hub.spans.append({
                "name": name, "start_ns": start, "end_ns": end,
                "parent": parent.name if parent is not None else None,
                **ids, "traces": frame.traces, "trace_s": frame.trace_s,
                "compiles": frame.compiles})


def _book(hub, label: str, traces: int, trace_s: float,
          compiles: int) -> None:
    if traces:
        hub.inc("jax_traces_total", traces, span=label)
        hub.inc("jax_trace_seconds_total", trace_s, span=label)
    if compiles:
        hub.inc("jax_compiles_total", compiles, span=label)


def _on_duration(event: str, duration: float, **_) -> None:
    """Book one JAX trace or compile to the innermost open span (its
    counters reach the hub when it closes), else at once to ``none``."""
    is_trace = event == TRACE_EVENT
    if not is_trace and event not in COMPILE_EVENTS:
        return
    frame = current()
    if frame is None:
        from repro.obs.hub import get_hub

        _book(get_hub(), "none", int(is_trace),
              duration if is_trace else 0.0, int(not is_trace))
    elif is_trace:
        frame.traces += 1
        frame.trace_s += duration
    else:
        frame.compiles += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
