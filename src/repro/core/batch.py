"""Batch oracles and baseline incremental algorithms (paper §2.3 comparisons).

* ``batch_kpca``      — eigh of the (optionally centered) gram matrix; the
  exactness oracle used by every test and the drift benchmark.
* ``rotated_eigh_step`` — the *dense small-problem* incremental baseline: the
  update to K' is expressed in the current eigenbasis Q = blockdiag(U, 1),
  the (m+1)x(m+1) projected matrix is eigendecomposed and U rotated.  This
  performs exactly the operation mix the paper attributes to Chin & Suter
  (2007) — one small eigh (~9m^3 flops) plus an m×m matmul (2m^3) — minus
  their extra eigh of the unadjusted kernel matrix, i.e. it is a *stronger*
  version of that baseline (~11m^3 vs their ~20m^3 vs ours ~8m^3).
* ``hoegaerts_step``  — the unadjusted two-rank-one-update scheme of
  Hoegaerts et al. (2007) coincides with Algorithm 1; provided as an alias.

All baselines produce exact eigendecompositions (up to fp error), so tests
cross-check all algorithms against each other and against ``batch_kpca``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import kernels_fn as kf
from repro.core.precision import MATMUL_PRECISION

Array = jax.Array


def batch_kpca(K: Array, *, adjusted: bool) -> tuple[Array, Array]:
    """Oracle: eigendecomposition (ascending) of K or the centered K'."""
    Keff = kf.center_gram(K) if adjusted else K
    return jnp.linalg.eigh(Keff)


def refit_state(state, spec: kf.KernelSpec, *, adjusted: bool):
    """From-scratch re-fit oracle: rebuild a padded ``KPCAState`` by batch
    KPCA of the stored active points X[:m] — the baseline the heal
    ladder's in-place ``health.resync`` is benchmarked against (resync
    skips the stream replay and the gram's host round-trip, but both end
    at the same eigensystem).  Returns a state with identical capacity,
    padding sentinels and running sums to a fresh ``inkpca.init_state``
    of the same points."""
    from repro.core import inkpca

    m = int(state.m)
    return inkpca.init_state(state.X[:m], state.L.shape[0], spec,
                             adjusted=adjusted, dtype=state.L.dtype)


@partial(jax.jit)
def rotated_eigh_step(L: Array, U: Array, Kprev: Array, Knew: Array
                      ) -> tuple[Array, Array]:
    """Chin–Suter-class baseline: one incremental step via projected eigh.

    L, U: eigendecomposition of the centered K' of the first m points
    Kprev: unadjusted m×m gram, Knew: unadjusted (m+1)×(m+1) gram.
    Returns eigendecomposition of the centered (m+1)×(m+1) K'.
    """
    m = L.shape[0]
    Kp_new = kf.center_gram(Knew)
    # Q = blockdiag(U, 1) spans R^{m+1}; project, eigh, rotate.
    Kp_old = jnp.matmul(U * L[None, :], U.T, precision=MATMUL_PRECISION)
    delta = Kp_new - jnp.pad(Kp_old, ((0, 1), (0, 1)))
    Q = jnp.pad(U, ((0, 1), (0, 1))).at[m, m].set(1.0)
    small = (jnp.diag(jnp.pad(L, (0, 1)))
             + jnp.matmul(jnp.matmul(Q.T, delta, precision=MATMUL_PRECISION),
                          Q, precision=MATMUL_PRECISION))
    lam, V = jnp.linalg.eigh(small)
    # one (m+1)x(m+1) matmul — the baseline's hot spot
    return lam, jnp.matmul(Q, V, precision=MATMUL_PRECISION)


# Alias: the unadjusted-case baseline of Hoegaerts et al. (2007) performs the
# same two symmetric rank-one updates as our Algorithm 1.
from repro.core.inkpca import update_unadjusted as hoegaerts_step  # noqa: E402,F401


def flop_model(m: int) -> dict[str, float]:
    """Leading-order flop counts per incremental step at size m (paper §3).

    Paper's accounting: a rank-one eigenvector update costs one m×m matmul
    (2m^3); QR-algorithm eigh ~ 9m^3; Chin & Suter: eigh(m+2) + eigh(m) +
    m×m matmul ~ 20m^3.
    """
    return {
        "ours_adjusted": 8.0 * m**3,        # 4 rank-one updates × 2m^3
        "ours_unadjusted": 4.0 * m**3,      # 2 rank-one updates × 2m^3
        "chin_suter_2007": 20.0 * m**3,     # paper's cited cost
        "rotated_eigh_baseline": 11.0 * m**3,  # eigh(m+1) + rotate
        "batch_eigh": 9.0 * m**3,           # recompute from scratch
    }
