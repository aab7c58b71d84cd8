"""Phase tracing — named spans that line up in Perfetto/TensorBoard.

``span("ingest")`` wraps ``jax.profiler.TraceAnnotation``, so when a
profiler trace is being captured (``jax.profiler.trace(...)`` or
TensorBoard's capture button) every host-side service phase shows up as
a named slice on the timeline, aligned with the device ops it
dispatched.  Without an active capture the annotation is free.

Pass a ``LatencyHistogram`` (``hist=``) to ALSO record the span's
wall-clock into the hub — one context manager, both sinks.
"""
from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation as trace_annotation


@contextlib.contextmanager
def span(name: str, *, hist=None, key=None):
    """Named phase scope.

    With ``hist`` (an ``obs.LatencyHistogram``) the span is timed into
    it under the compile-split ``key`` and yields the histogram's timing
    handle (call ``.sync(arrays)`` before exit to block on device
    results); without it the span only annotates the profiler timeline
    and yields None.
    """
    if hist is not None:
        with hist.timed(key=key, name=name) as handle:
            yield handle
        return
    with trace_annotation(name):
        yield None
