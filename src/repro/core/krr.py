"""Incremental kernel ridge regression — the paper's §3 claim made
concrete: "any incremental algorithm for the eigendecomposition of the
kernel matrix can be applied where the explicit or implicit inverse of the
same is required, such as kernel regression and kernel SVM."

The KRR coefficients are α = (K + λI)⁻¹ y. With the maintained
eigendecomposition K = U Λ Uᵀ (Algorithm 1 state), the solve is a
diagonal rescale

    α = U (Λ + λI)⁻¹ Uᵀ y

so adding a data point costs the rank-one update (4m² + the O(m³)
rotation already paid for KPCA) plus an O(m²) re-solve — and λ can be
*swept for free* (one diagonal rescale per λ), which is how the
regularization path is usually chosen in practice.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import engine as eng
from repro.core import inkpca, kernels_fn as kf, rankone
from repro.core.precision import MATMUL_PRECISION

Array = jax.Array


class KRRState(NamedTuple):
    kpca: inkpca.KPCAState       # eigendecomposition of K_{m,m} (Alg. 1)
    y: Array                     # (M,) targets, zero-padded


def init_krr(x0: Array, y0: Array, capacity: int, spec: kf.KernelSpec,
             *, dtype=jnp.float64) -> KRRState:
    kpca = inkpca.init_state(x0, capacity, spec, adjusted=False, dtype=dtype)
    y = jnp.zeros((capacity,), dtype).at[: y0.shape[0]].set(
        y0.astype(dtype))
    return KRRState(kpca=kpca, y=y)


def add_point(state: KRRState, x_new: Array, y_new: Array,
              spec: kf.KernelSpec, *,
              plan: eng.UpdatePlan = eng.DEFAULT_PLAN) -> KRRState:
    a, k_new = inkpca._masked_row(state.kpca, x_new, spec)
    m = state.kpca.m
    kpca = inkpca.update_unadjusted(state.kpca, a, k_new, x_new, plan=plan)
    y = state.y.at[m].set(jnp.asarray(y_new, state.y.dtype))
    return KRRState(kpca=kpca, y=y)


def coefficients(state: KRRState, lam: float) -> Array:
    """α = U (Λ + λ)⁻¹ Uᵀ y — O(m²) given the maintained eigenpairs."""
    st = state.kpca
    M = st.L.shape[0]
    mask = rankone.active_mask(M, st.m)
    y = jnp.where(mask, state.y, 0.0)
    z = jnp.matmul(st.U.T, y, precision=MATMUL_PRECISION)
    inv = jnp.where(mask, 1.0 / (st.L + lam), 0.0)
    return jnp.matmul(st.U, inv * z, precision=MATMUL_PRECISION)


def predict(state: KRRState, x: Array, lam: float,
            spec: kf.KernelSpec) -> Array:
    """f(x) = k(x, X) α for new points x: (n, d).

    Publish-then-query: the compiled serving head answers against an
    ephemeral snapshot, so a prediction from the state is bit-identical
    to one from a snapshot published from it."""
    return snapshot_predict(publish_predict(state, lam), x, spec)


def publish_predict(state: KRRState, lam: float, *,
                    generation: int | Array = 0):
    """Freeze the KRR predict head into a ``serving.ServingSnapshot``:
    S = α[:, None] (the maintained-eigenpair solve runs once, at
    publication), so serving predictions are plain snapshot queries —
    f(x) = k(x, X_masked) @ α — with no per-call O(M²) coefficient
    solve, and immutable under concurrent ingest into the working state."""
    from repro.core import serving

    st = state.kpca
    alpha = coefficients(state, lam)
    return serving.ServingSnapshot(
        S=alpha[:, None].astype(st.X.dtype), X=st.X, m=st.m, affine=None,
        generation=jnp.asarray(generation, jnp.int32))


def snapshot_predict(snap, x: Array, spec: kf.KernelSpec, *,
                     plan: eng.UpdatePlan | None = None) -> Array:
    """f(x) for a published KRR snapshot: (n, d) -> (n,)."""
    from repro.core import serving

    return serving.query(snap, x, spec=spec, plan=plan)[:, 0]


def loocv_residuals(state: KRRState, lam: float) -> Array:
    """Leave-one-out residuals in closed form — e_i = (y−Kα)_i/(1−H_ii)
    with the hat diagonal H_ii = Σ_j U_ij² λ_j/(λ_j+λ) from the maintained
    eigenpairs. The streaming λ-selection loop this enables is the same
    'empirical evaluation' story the paper tells for Nyström subset size."""
    st = state.kpca
    M = st.L.shape[0]
    mask = rankone.active_mask(M, st.m)
    lam_safe = jnp.where(mask, st.L, 0.0)
    w = lam_safe / (lam_safe + lam)
    H_diag = jnp.sum((st.U * st.U) * w[None, :], axis=1)
    alpha = coefficients(state, lam)
    resid = jnp.where(mask, state.y, 0.0) - lam_safe_dot(state, alpha)
    denom = jnp.maximum(1.0 - H_diag, 1e-12)
    return jnp.where(mask, resid / denom, 0.0)


def lam_safe_dot(state: KRRState, alpha: Array) -> Array:
    """K α via the maintained eigenpairs (avoids storing K)."""
    st = state.kpca
    M = st.L.shape[0]
    mask = rankone.active_mask(M, st.m)
    lam_active = jnp.where(mask, st.L, 0.0)
    proj = jnp.matmul(st.U.T, alpha, precision=MATMUL_PRECISION)
    return jnp.matmul(st.U, lam_active * proj, precision=MATMUL_PRECISION)
