"""Pure-jnp oracles for the Nyström reconstruction / fused transform kernels."""
import jax
import jax.numpy as jnp

from repro.core import kernels_fn as kf
from repro.core.precision import MATMUL_PRECISION


def scaled_gram_ref(b: jax.Array, s: jax.Array) -> jax.Array:
    return jnp.matmul(b * s[None, :], b.T, precision=MATMUL_PRECISION)


def transform_project_ref(xq: jax.Array, x: jax.Array, s: jax.Array,
                          num_active: jax.Array, *, spec: kf.KernelSpec
                          ) -> tuple[jax.Array, jax.Array]:
    """(Y, rowsum) oracle — materializes the masked query gram."""
    dtype = s.dtype
    kq = kf.gram_block(xq.astype(dtype), x.astype(dtype), spec=spec)
    mask = jnp.arange(x.shape[0]) < num_active
    kq = jnp.where(mask[None, :], kq, 0.0).astype(dtype)
    return jnp.matmul(kq, s, precision=MATMUL_PRECISION), jnp.sum(kq, axis=1)
