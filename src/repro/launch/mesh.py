"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module does not touch jax device state — the dry-run must set XLA_FLAGS
*before* the first jax device query.

Topology (TPU v5e pods):
  single-pod: (data=16, model=16)            = 256 chips
  multi-pod:  (pod=2, data=16, model=16)     = 512 chips
The 'pod' axis carries pure data parallelism (gradient all-reduce, int8
compressed), 'data' carries FSDP + batch, 'model' carries TP/EP/sequence.
"""
from __future__ import annotations

import jax

from repro.distributed.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Whatever-fits mesh for CPU smoke runs (1 device -> (1, 1))."""
    n = len(jax.devices())
    return make_mesh((n // model, model), ("data", "model"))
