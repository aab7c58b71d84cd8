"""Pure-jnp oracles for the fused Cauchy eigenvector rotations."""
import jax
import jax.numpy as jnp

from repro.core.precision import MATMUL_PRECISION


def eigvec_rotate_ref(u: jax.Array, zhat: jax.Array, d: jax.Array,
                      lam: jax.Array, inv: jax.Array,
                      tau: jax.Array | None = None) -> jax.Array:
    """Materialize W then matmul — the unfused baseline the kernel beats.

    ``u`` may be square (M, M) or a rectangular (R, M) row block; the
    product is over u's columns either way.  Column j's root is
    lam_j + tau_j (tau defaults to 0).
    """
    if tau is None:
        tau = jnp.zeros_like(lam)
    tiny = jnp.finfo(zhat.dtype).tiny
    den = (d[:, None] - lam[None, :]) - tau[None, :]
    den = jnp.where(jnp.abs(den) < tiny, jnp.where(den < 0, -tiny, tiny),
                    den)
    W = zhat[:, None] / den
    return jnp.matmul(u, W, precision=MATMUL_PRECISION) * inv[None, :]


def eigvec_project_ref(u: jax.Array, v: jax.Array,
                       num_active: jax.Array | None = None,
                       row_offset: jax.Array | None = None) -> jax.Array:
    """P = Uᵀ V with rows >= num_active (global index) masked to zero —
    the unfused oracle for ``eigvec_project``.  ``u``/``v`` may be a
    rectangular (R, ·) row block whose first global row is ``row_offset``."""
    if num_active is not None:
        r0 = 0 if row_offset is None else row_offset
        rows = r0 + jnp.arange(u.shape[0])
        v = jnp.where((rows < num_active)[:, None], v, 0.0)
    return jnp.matmul(u.T, v, precision=MATMUL_PRECISION)


def pruned_region_mask(R: int, M: int, m, row_offset=None, *,
                       block: int) -> tuple[jax.Array, jax.Array]:
    """(row_mask (R,), col_mask (M,)) of the tiles the pruned kernels WRITE.

    True = inside the active tile range (kernel computes real values);
    False = pruned (kernel writes exact zeros).  Mirrors ``_tile_counts``
    in eigvec_update.py so tests and callers can assert the contract:
    within the active region the kernel matches ``eigvec_rotate_ref``,
    outside it the output is zero (which is also the true value for rows
    past the active prefix of active columns).
    """
    r0 = 0 if row_offset is None else row_offset
    m = jnp.asarray(m, jnp.int32)
    rows_active = jnp.clip(m - r0, 0, R)
    g_rows = -(-rows_active // block)
    g_cols = -(-m // block)
    row_mask = jnp.arange(R) < g_rows * block
    col_mask = jnp.arange(M) < g_cols * block
    return row_mask, col_mask


def cauchy_factor_ref(z: jax.Array, d: jax.Array, lam: jax.Array,
                      inv: jax.Array, defl: jax.Array | None = None,
                      cid: jax.Array | None = None,
                      tau: jax.Array | None = None) -> jax.Array:
    """Dense normalized Cauchy factor with deflated identity columns.

    W[k, j] = z[k]·inv[j]/((d[k]-lam[j])-tau[j]) (tau defaults to 0);
    columns with defl[j] != 0 are replaced by e_{cid[j]} (cid defaults to
    j).  Matches the in-VMEM tile generation of ``eigvec_rotate2``
    including its denominator guard (exact zeros only).
    """
    M = z.shape[0]
    tiny = jnp.finfo(z.dtype).tiny
    if tau is None:
        tau = jnp.zeros_like(lam)
    den = (d[:, None] - lam[None, :]) - tau[None, :]
    den = jnp.where(jnp.abs(den) < tiny, jnp.where(den < 0, -tiny, tiny),
                    den)
    W = z[:, None] * inv[None, :] / den
    if defl is None:
        return W
    if cid is None:
        cid = jnp.arange(M, dtype=jnp.int32)
    E = (jnp.arange(M)[:, None] == cid[None, :]).astype(W.dtype)
    return jnp.where(defl[None, :] > 0, E, W)


def eigvec_rotate2_ref(u: jax.Array,
                       z1: jax.Array, d1: jax.Array, lam1: jax.Array,
                       inv1: jax.Array, defl1: jax.Array, cid1: jax.Array,
                       z2: jax.Array, d2: jax.Array, lam2: jax.Array,
                       inv2: jax.Array, defl2: jax.Array,
                       cid2: jax.Array, tau1: jax.Array | None = None,
                       tau2: jax.Array | None = None) -> jax.Array:
    """Two sequential dense rotations — the oracle for ``eigvec_rotate2``."""
    W1 = cauchy_factor_ref(z1, d1, lam1, inv1, defl1, cid1, tau=tau1)
    W2 = cauchy_factor_ref(z2, d2, lam2, inv2, defl2, cid2, tau=tau2)
    return jnp.matmul(jnp.matmul(u, W1, precision=MATMUL_PRECISION), W2,
                      precision=MATMUL_PRECISION)
