"""Jit'd public wrapper for the Nyström reconstruction kernel."""
from __future__ import annotations

import jax

from repro.kernels.dispatch import route as _route
from repro.kernels.nystrom_recon.nystrom_recon import scaled_gram as _pallas
from repro.kernels.nystrom_recon.ref import (scaled_gram_ref,
                                             transform_project_ref)
from repro.kernels.nystrom_recon.transform_batch import \
    transform_project as _tb_pallas
from repro.kernels.rbf_gram.krow_fused import PALLAS_KERNELS


def scaled_gram(b: jax.Array, s: jax.Array, *, force: str | None = None
                ) -> jax.Array:
    route = _route("scaled_gram", force, b, s)
    if route == "ref":
        return scaled_gram_ref(b, s)
    if route == "interpret":
        return _pallas(b, s, interpret=True)
    return _pallas(b, s)


def transform_project(xq: jax.Array, x: jax.Array, s: jax.Array,
                      num_active: jax.Array, *, spec,
                      force: str | None = None
                      ) -> tuple[jax.Array, jax.Array]:
    """Fused masked query gram + projection (Y, rowsum) — see
    ``transform_batch.py``."""
    if spec.name not in PALLAS_KERNELS:
        force = "ref"    # non-stationary kernels: reference epilogue only
    route = _route("transform_project", force, xq, x, s)
    if route == "ref":
        return transform_project_ref(xq, x, s, num_active, spec=spec)
    if route == "interpret":
        return _tb_pallas(xq, x, s, num_active, spec=spec, interpret=True)
    return _tb_pallas(xq, x, s, num_active, spec=spec)
