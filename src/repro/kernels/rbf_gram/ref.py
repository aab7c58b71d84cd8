"""Pure-jnp oracles for the tiled RBF gram / fused k-row kernels."""
import jax
import jax.numpy as jnp

from repro.core import kernels_fn as kf
from repro.core.precision import MATMUL_PRECISION


def rbf_gram_ref(x: jax.Array, y: jax.Array, sigma: jax.Array) -> jax.Array:
    xn = jnp.sum(x * x, axis=-1)[:, None]
    yn = jnp.sum(y * y, axis=-1)[None, :]
    xy = jnp.matmul(x, y.T, precision=MATMUL_PRECISION)
    d2 = jnp.maximum(xn + yn - 2.0 * xy, 0.0)
    return jnp.exp(-d2 / sigma)


def krow_project_ref(u: jax.Array, x: jax.Array, x_new: jax.Array,
                     aux: jax.Array, num_active: jax.Array,
                     row_offset: jax.Array | None = None, *,
                     spec: kf.KernelSpec) -> tuple[jax.Array, jax.Array]:
    """(a, P) oracle — uses kernels_fn.gram_block so the masked row is
    bitwise the unfused engine.masked_row value."""
    dtype = u.dtype
    R = u.shape[0]
    r0 = (jnp.zeros((), jnp.int32) if row_offset is None
          else jnp.asarray(row_offset, jnp.int32))
    rows = r0 + jnp.arange(R, dtype=jnp.int32)
    kr = kf.gram_block(x.astype(dtype), x_new.astype(dtype)[None, :],
                       spec=spec)[:, 0]
    a = jnp.where(rows < num_active, kr, 0.0).astype(dtype)
    auxm = jnp.where(rows[:, None] < num_active, aux.astype(dtype), 0.0)
    v = jnp.concatenate([a[:, None], auxm], axis=1)
    return a, jnp.matmul(u.T, v, precision=MATMUL_PRECISION)
