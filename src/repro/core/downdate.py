"""Decremental updates: remove a point from the maintained eigensystem.

The paper's rank-one machinery is sign-symmetric — Algorithm 1 folds a
point *in* by expanding with the eigenpair (k/4, e_m) and applying the
±sigma pair (v1, +4/k), (v2, −4/k); the exact inverse folds it back *out*
by applying (v2, +4/k), (v1, −4/k) and then *contracting* the decoupled
(k/4, e_q) eigenpair.  Algorithm 2 (mean-adjusted) composes the same way:
the expansion pair inverts first, then the mean-adjustment pair with its
sigmas negated and order swapped.  Streaming KPCA under this kind of
eviction/forgetting is the regime of Ghashami et al. (1512.05059); here
the downdate is *exact* (up to rounding), not a sketch.

Pipeline for ``downdate(state, i)``:

1. **Permute** point i to the active boundary q = m−1 (a cyclic shift
   that preserves the arrival order of the survivors).  K → P K Pᵀ maps
   the eigensystem to (L, P U): a row permutation of U, X and K1 confined
   to the active prefix, so every padding invariant — and therefore the
   Pallas kernels' active-tile pruning — survives untouched.
2. **Inverse pair(s)** via the shared ``engine.apply_pair`` machinery
   (fused double rotation or sequential, per the plan): after them the
   maintained matrix is exactly block-diagonal with row q decoupled.
3. **Contract**: rotate the eigensystem so the decoupled eigenpair
   becomes the exact identity pair (sentinel, e_q), then shrink m.  The
   rotation is a single Householder on U's *columns* built from row q of
   U (O(M²), no extra matmul): in exact arithmetic row q of the active
   columns is already ±e_{j*} and the reflector is the identity; under
   degeneracy (the contracted eigenvalue collides with the spectrum) it
   rotates only inside the near-degenerate eigenspace — the same
   error-versus-gap trade as the dlaed2 cluster merge in ``rankone``.

Stage names (``jax.named_scope``, as in ``rankone``): ``kpca.downdate``
the permutation to the boundary and the contraction; the boundary row is
``kpca.krow`` and the inverse pairs keep ``rankone``'s own stages, the
innermost name being the one that counts.

Cost matches the forward update: O(M_b³) in the rotation at the active
bucket — ``Engine.downdate`` slices to the bucket holding m, and the next
*update* re-buckets downward automatically since bucket choice reads the
(now smaller) active count.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import engine as eng
from repro.core import kernels_fn as kf, rankone
from repro.core.precision import MATMUL_PRECISION

Array = jax.Array


def boundary_perm(i: Array, m: Array, M: int) -> Array:
    """Row order moving index ``i`` to the active boundary q = m−1.

    Survivors keep their relative (arrival) order: the returned ``order``
    satisfies new = old[order] = [0..i−1, i+1..q, i, q+1..M−1].  Inactive
    rows never move.  Pure function of (i, m), so callers maintaining
    side arrays (ages rings, Nyström Knm columns) apply the same order.
    """
    return _move_key_order(i, m - 1, M)


def _move_key_order(src: Array, dst: Array, M: int) -> Array:
    """argsort order moving slot ``src`` to just after slot ``dst``
    (src <= dst), everything else keeping its relative order.  Integer
    keys 2·idx and 2·dst + 1 are exact in int32, where a float key
    would silently round without x64."""
    idx = jnp.arange(M, dtype=jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    key = jnp.where(idx == src, 2 * dst + 1, 2 * idx)
    return jnp.argsort(key)


@jax.named_scope("kpca.downdate")
def permute_to_boundary(state, i: Array):
    """Apply ``boundary_perm`` to the state's row-indexed arrays."""
    order = boundary_perm(i, state.m, state.L.shape[0])
    return state._replace(U=state.U[order, :], K1=state.K1[order],
                          X=state.X[order])


@jax.named_scope("kpca.downdate")
def contract_rows(L: Array, U: Array, w: Array, m: Array, *,
                  row_ids: Array | None = None
                  ) -> tuple[Array, Array, Array]:
    """Contraction core on a ROW BLOCK of the eigenvector matrix.

    Precondition: the maintained matrix is block-diagonal with row
    q = m−1 decoupled (the inverse pair has just run), so exactly one
    active eigenvector carries the e_q direction.  ``w`` is the
    (replicated) global row q of U masked to the active columns — a unit
    vector; a Householder H concentrates that direction into column
    j* = argmax |w|, which then *is* ±e_q by orthogonality.  The column
    is permuted to position q and the identity row/column forced
    exactly, restoring the padding invariants for the shrunk system.
    When w is already ±e_{j*} (the generic case) H ≈ a column flip and
    the contraction is exact; otherwise the rotation mixes only columns
    where w has mass — near-degenerate eigenvalues — erring by at most
    the cluster width, the standard deflation trade.

    The reflector and permutation act on U's COLUMNS, so ``U`` may be
    any row block (the distributed path passes its local (R, M) shard
    with ``row_ids`` naming the block's global rows; None = the full
    square matrix).  The LAPACK sign choice (reflect onto
    −sign(w_{j*})·e_{j*}, ‖u‖² ≈ 4) avoids the catastrophic
    cancellation of the same-sign target (‖u‖² ~ coupling²) — the
    target's sign is irrelevant since the identity pair is forced.
    """
    M = L.shape[0]
    dtype = L.dtype
    q = m - 1
    if row_ids is None:
        row_ids = jnp.arange(U.shape[0])
    j_star = jnp.argmax(jnp.abs(w))
    sgn = jnp.where(w[j_star] < 0, -1.0, 1.0).astype(dtype)
    u = w + sgn * jax.nn.one_hot(j_star, M, dtype=dtype)
    unorm2 = jnp.sum(u * u)
    coef = jnp.where(unorm2 > jnp.finfo(dtype).tiny, 2.0 / unorm2, 0.0)
    # U @ H, a rank-one apply
    U = U - coef * jnp.outer(jnp.matmul(U, u, precision=MATMUL_PRECISION), u)

    # Column j* -> position q; columns between shift left by one (the
    # order of boundary_perm, on the column axis).
    order = _move_key_order(j_star, q, M)
    U = U[:, order]
    L = L[order]

    # Force the exact identity pair at position q (rounding-level cleanup:
    # by orthogonality the column already is ±e_q and row q of every other
    # active column is ~0).  Both forcings are local to the row block.
    U = U.at[:, q].set((row_ids == q).astype(dtype))
    e_qM = jax.nn.one_hot(q, M, dtype=dtype)
    U = jnp.where((row_ids == q)[:, None], e_qM[None, :], U)
    m_new = m - 1
    L = rankone.sentinelize(L, m_new, jnp.zeros((), dtype))
    return L, U, m_new


@jax.named_scope("kpca.downdate")
def contract_last(L: Array, U: Array, m: Array) -> tuple[Array, Array, Array]:
    """Remove the decoupled boundary eigenpair of the full square system
    and shrink m by one (see ``contract_rows``)."""
    mask = rankone.active_mask(L.shape[0], m)
    w = jnp.where(mask, U[m - 1, :], 0.0)
    return contract_rows(L, U, w, m)


@jax.named_scope("kpca.krow")
def _boundary_row(state, spec: kf.KernelSpec) -> tuple[Array, Array, Array]:
    """Kernel row of the boundary point against the survivors.

    Returns (a, k_new, sum_a): a is zero at and beyond q = m−1, matching
    exactly the masked row the forward update consumed when this point
    streamed in (same stored X rows, elementwise kernel).
    """
    M = state.L.shape[0]
    q = state.m - 1
    x_ev = state.X[q]
    k_full = kf.kernel_row(x_ev, state.X, spec=spec)
    k_full = jnp.where(rankone.active_mask(M, state.m), k_full, 0.0)
    a = jnp.where(jnp.arange(M) < q, k_full, 0.0)
    return a, k_full[q], jnp.sum(a)


@partial(jax.jit, static_argnames=("spec", "plan"))
def downdate_unadjusted(state, spec: kf.KernelSpec, *,
                        plan: eng.UpdatePlan = eng.DEFAULT_PLAN):
    """Inverse of Algorithm 1 for the boundary point (row m−1)."""
    M = state.L.shape[0]
    q = state.m - 1
    a, k_new, sum_a = _boundary_row(state, spec)
    kn = jnp.maximum(k_new, jnp.finfo(state.L.dtype).tiny)

    v1 = a.at[q].set(kn / 2.0)
    v2 = a.at[q].set(kn / 4.0)
    sigma = 4.0 / kn
    L, U = eng.apply_pair(state.L, state.U, v2, sigma, v1, -sigma, state.m,
                          plan=plan)
    L, U, m_new = contract_last(L, U, state.m)

    K1 = jnp.where(jnp.arange(M) < q, state.K1 - a, 0.0)
    S = state.S - 2.0 * sum_a - k_new
    X = state.X.at[q].set(jnp.zeros_like(state.X[q]))
    return state._replace(L=L, U=U, m=m_new, S=S, K1=K1, X=X)


@partial(jax.jit, static_argnames=("spec", "plan"))
def downdate_adjusted(state, spec: kf.KernelSpec, *,
                      plan: eng.UpdatePlan = eng.DEFAULT_PLAN):
    """Inverse of Algorithm 2 for the boundary point (row m−1).

    Forward order was: mean-adjustment pair at m, expansion, new-row pair
    at m+1.  The inverse runs the new-row pair first (negated sigmas,
    swapped order), contracts the expansion eigenpair, then inverts the
    mean-adjustment pair — whose u vector is rebuilt from the *pre*-add
    bookkeeping (S, K1) recovered from the maintained sums.
    """
    M = state.L.shape[0]
    dtype = state.L.dtype
    q = state.m - 1
    mask_m = rankone.active_mask(M, state.m)
    mf_post = state.m.astype(dtype)

    a, k_new, sum_a = _boundary_row(state, spec)

    # --- Invert step 4: the expansion pair (paper eq. (3)). ---
    k_vec = a.at[q].set(k_new)
    v = k_vec - (jnp.sum(k_vec) + state.K1 - state.S / mf_post) / mf_post
    v = jnp.where(mask_m, v, 0.0)
    v0 = v[q]
    v0 = jnp.where(jnp.abs(v0) < jnp.finfo(dtype).eps,
                   jnp.finfo(dtype).eps, v0)
    v1 = v.at[q].set(v0 / 2.0)
    v2 = v.at[q].set(v0 / 4.0)
    sigma = 4.0 / v0
    L, U = eng.apply_pair(state.L, state.U, v2, sigma, v1, -sigma, state.m,
                          plan=plan)
    L, U, m_new = contract_last(L, U, state.m)

    # --- Invert step 1: the mean-adjustment pair, at m_new actives. ---
    S_pre = state.S - 2.0 * sum_a - k_new
    mask_q = rankone.active_mask(M, m_new)
    K1_pre = jnp.where(mask_q, state.K1 - a, 0.0)
    mf = m_new.astype(dtype)
    C = -S_pre / mf**2 + state.S / (mf + 1.0) ** 2
    u = K1_pre / (mf * (mf + 1.0)) - a / (mf + 1.0) + 0.5 * C
    u = jnp.where(mask_q, u, 0.0)
    ones_u_p = jnp.where(mask_q, 1.0 + u, 0.0)
    ones_u_m = jnp.where(mask_q, 1.0 - u, 0.0)
    half = jnp.asarray(0.5, dtype)
    L, U = eng.apply_pair(L, U, ones_u_m, half, ones_u_p, -half, m_new,
                          plan=plan)

    X = state.X.at[q].set(jnp.zeros_like(state.X[q]))
    return state._replace(L=L, U=U, m=m_new, S=S_pre, K1=K1_pre, X=X)


@partial(jax.jit, static_argnames=("spec", "adjusted", "plan"))
def downdate(state, i: Array, spec: kf.KernelSpec, *, adjusted: bool,
             plan: eng.UpdatePlan = eng.DEFAULT_PLAN):
    """Remove point ``i`` (0 ≤ i < m) from the maintained eigensystem.

    Fully traced (i may be a device scalar), so it vmaps across tenants —
    ``engine.StreamBatch`` uses exactly that for masked batched
    downdates.  Requires m ≥ 2 (the mean-adjusted inverse needs at least
    one survivor); callers enforce this on the host.
    """
    state = permute_to_boundary(state, i)
    fn = downdate_adjusted if adjusted else downdate_unadjusted
    return fn(state, spec, plan=plan)
