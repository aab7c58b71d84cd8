"""Jit'd public wrappers for the fused eigenvector rotation kernels.

Dispatch (``repro.kernels.dispatch.route``): a TPU runs the compiled
Pallas kernels, any other backend the pure-jnp oracles; CPU tests run the
kernel bodies in interpret mode through ``REPRO_PALLAS_FORCE=interpret``
(the interpreter is Python-slow; numerics are identical).
"""
from __future__ import annotations

import jax

from repro.kernels.dispatch import route as _route
from repro.kernels.eigvec_update.eigvec_update import (eigvec_project,
                                                       eigvec_rotate,
                                                       eigvec_rotate2)
from repro.kernels.eigvec_update.ref import (eigvec_project_ref,
                                             eigvec_rotate2_ref,
                                             eigvec_rotate_ref)


def rotate_vectors(u: jax.Array, zhat: jax.Array, d: jax.Array,
                   lam: jax.Array, inv: jax.Array,
                   num_active: jax.Array | None = None,
                   row_offset: jax.Array | None = None, *,
                   tau: jax.Array | None = None,
                   force: str | None = None) -> jax.Array:
    """C = U @ (diag-normalized Cauchy factor); column j's root is
    lam_j + tau_j (``tau`` defaults to 0, see ``rankone._Factor``).

    ``u`` may be square (M, M) or a rectangular (R, M) row block whose
    first global row is ``row_offset`` (the distributed row-sharded
    shape).  ``num_active`` enables active-tile grid pruning along both
    axes (see eigvec_update.py); pruned columns come back as zeros for
    the caller to overwrite.

    force in {None, 'pallas', 'interpret', 'ref'} overrides dispatch; the
    REPRO_PALLAS_FORCE env var does the same (tests set it to 'interpret'
    so the real kernel body executes on CPU).
    """
    route = _route("eigvec_rotate", force, u, zhat, d, lam, inv)
    if route == "ref":
        return eigvec_rotate_ref(u, zhat, d, lam, inv, tau)
    return eigvec_rotate(u, zhat, d, lam, inv, num_active, row_offset, tau,
                         interpret=route == "interpret")


def rotate_vectors2(u: jax.Array,
                    z1: jax.Array, d1: jax.Array, lam1: jax.Array,
                    inv1: jax.Array, defl1: jax.Array, cid1: jax.Array,
                    z2: jax.Array, d2: jax.Array, lam2: jax.Array,
                    inv2: jax.Array, defl2: jax.Array, cid2: jax.Array,
                    num_active: jax.Array | None = None,
                    row_offset: jax.Array | None = None, *,
                    tau1: jax.Array | None = None,
                    tau2: jax.Array | None = None,
                    force: str | None = None) -> jax.Array:
    """Fused double rotation C = U @ W1n @ W2n (eq. (2)/(3) back-to-back).

    Same dispatch and rectangular-operand contract as ``rotate_vectors``.
    Deflated columns are generated as identity columns e_{cid[j]} inside
    the kernel, so the intermediate U @ W1n never exists in HBM.
    """
    args = (u, z1, d1, lam1, inv1, defl1, cid1,
            z2, d2, lam2, inv2, defl2, cid2)
    route = _route("eigvec_rotate2", force, *args)
    if route == "ref":
        return eigvec_rotate2_ref(*args, tau1, tau2)
    return eigvec_rotate2(*args, num_active, row_offset, tau1, tau2,
                          interpret=route == "interpret")


def project_vectors(u: jax.Array, v: jax.Array,
                    num_active: jax.Array | None = None,
                    row_offset: jax.Array | None = None, *,
                    force: str | None = None) -> jax.Array:
    """P = Uᵀ V (row-masked at ``num_active``) — the post-rotation
    projection of Algorithm 2's second ±sigma pair as one rect-pruned
    kernel pass instead of a dense einsum over the (M, M) eigenvectors.

    Same dispatch and rectangular-operand contract as ``rotate_vectors``;
    pruned output rows (>= the active tile range) come back as exact
    zeros, their true value.  Row-sharded callers psum the partials.
    """
    route = _route("eigvec_project", force, u, v)
    if route == "ref":
        return eigvec_project_ref(u, v, num_active, row_offset)
    if route == "interpret":
        return eigvec_project(u, v, num_active, row_offset, interpret=True)
    return eigvec_project(u, v, num_active, row_offset)
