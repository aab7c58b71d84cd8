"""Which device work belongs to which layer, for the per-layer readers.

The rank-one update layer is the device time of the programs that fold
and evict points (the program's jitted batched steps, by module name);
serving is the device time of the operations that ran inside a
``bench.query`` span.  Dot-like operations are the rotations and
projections: matrix products, which a TPU trace names as output fusions
(``kind=kOutput``, a dot or convolution with its consumers fused) or as
bare ``convolution``/``dot`` ops, and a CPU trace as ``dot``.
"""
from __future__ import annotations

INGEST_MODULES = ("_batched_update", "_batched_downdate")
INGEST_SPANS = ("bench.ingest", "bench.update", "bench.publish")


def is_ingest(op) -> bool:
    return any(p in op.module for p in INGEST_MODULES)


def is_dot(op) -> bool:
    text = f"{op.category} {op.name}"
    return ("kind=kOutput" in text or "convolution" in text
            or "dot" in text.lower())


def per_step_ms(run, pred):
    """Device milliseconds of the ops ``pred`` picks, per ingest step;
    None where the window had no step or the trace no such op."""
    ops = [o for o in run.trace.ops if pred(o)]
    if not run.steps or not ops:
        return None
    return sum(o.dur for o in ops) / 1e6 / run.steps
