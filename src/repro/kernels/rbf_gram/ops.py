"""Jit'd public wrapper for the RBF gram kernel (dispatch as eigvec_update)."""
from __future__ import annotations

import jax

from repro.kernels.dispatch import route as _route
from repro.kernels.rbf_gram.krow_fused import PALLAS_KERNELS
from repro.kernels.rbf_gram.krow_fused import krow_project as _krow_pallas
from repro.kernels.rbf_gram.rbf_gram import rbf_gram
from repro.kernels.rbf_gram.ref import krow_project_ref, rbf_gram_ref


def gram(x: jax.Array, y: jax.Array, sigma, *, force: str | None = None
         ) -> jax.Array:
    route = _route("rbf_gram", force, x, y, sigma)
    if route == "ref":
        return rbf_gram_ref(x, y, sigma)
    if route == "interpret":
        return rbf_gram(x, y, sigma, interpret=True)
    return rbf_gram(x, y, sigma)


def krow_project(u: jax.Array, x: jax.Array, x_new: jax.Array,
                 aux: jax.Array, num_active: jax.Array,
                 row_offset: jax.Array | None = None, *, spec,
                 force: str | None = None) -> tuple[jax.Array, jax.Array]:
    """Fused masked kernel row + projection P = U^T [a | aux]."""
    if spec.name not in PALLAS_KERNELS:
        force = "ref"    # non-stationary kernels: reference epilogue only
    route = _route("krow_project", force, u, x, x_new, aux)
    if route == "ref":
        return krow_project_ref(u, x, x_new, aux, num_active, row_offset,
                                spec=spec)
    if route == "interpret":
        return _krow_pallas(u, x, x_new, aux, num_active, row_offset,
                            spec=spec, interpret=True)
    return _krow_pallas(u, x, x_new, aux, num_active, row_offset, spec=spec)
