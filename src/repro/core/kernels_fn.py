"""Kernel functions k(x, y) and related utilities.

The paper uses the RBF kernel k(x,y) = exp(-||x-y||^2 / sigma) with sigma set
by the median heuristic (median of pairwise squared distances over a subset).
We additionally provide linear, polynomial and Matern-3/2 kernels so the
incremental eigendecomposition machinery is exercised on kernels with
non-constant diagonal (k(x,x) != 1), which the paper notes as the general case.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.precision import MATMUL_PRECISION

Array = jax.Array


@dataclass(frozen=True)
class KernelSpec:
    """Declarative kernel configuration (hashable, jit-static)."""

    name: str = "rbf"
    sigma: float = 1.0          # RBF / matern bandwidth
    degree: int = 3             # polynomial degree
    coef0: float = 1.0          # polynomial bias
    scale: float = 1.0          # output scale

    def fn(self) -> Callable[[Array, Array], Array]:
        return functools.partial(gram_block, spec=self)


def _sqdist(x: Array, y: Array) -> Array:
    """Pairwise squared euclidean distances, (n,d),(m,d) -> (n,m)."""
    xn = jnp.sum(x * x, axis=-1)[:, None]
    yn = jnp.sum(y * y, axis=-1)[None, :]
    d2 = xn + yn - 2.0 * jnp.matmul(x, y.T, precision=MATMUL_PRECISION)
    return jnp.maximum(d2, 0.0)


def gram_block(x: Array, y: Array, *, spec: KernelSpec) -> Array:
    """Dense gram block K[i,j] = k(x_i, y_j). Pure-jnp reference path.

    The tiled Pallas kernel in ``repro.kernels.rbf_gram`` implements the RBF
    case; this function is the oracle for it and the general fallback.
    """
    if spec.name == "rbf":
        return spec.scale * jnp.exp(-_sqdist(x, y) / spec.sigma)
    if spec.name == "linear":
        return spec.scale * jnp.matmul(x, y.T, precision=MATMUL_PRECISION)
    if spec.name == "poly":
        xy = jnp.matmul(x, y.T, precision=MATMUL_PRECISION)
        return spec.scale * (xy + spec.coef0) ** spec.degree
    if spec.name == "matern32":
        r = jnp.sqrt(_sqdist(x, y) + 1e-30)
        a = jnp.sqrt(3.0) * r / spec.sigma
        return spec.scale * (1.0 + a) * jnp.exp(-a)
    raise ValueError(f"unknown kernel {spec.name!r}")


def kernel_row(x_new: Array, xs: Array, *, spec: KernelSpec) -> Array:
    """a = [k(x_1, x_new), ..., k(x_m, x_new)] — the streaming hot path."""
    return gram_block(xs, x_new[None, :], spec=spec)[:, 0]


def constant_diag(spec: KernelSpec) -> float | None:
    """k(x, x) when it is input-independent (stationary kernels: RBF,
    Matérn), else None — lets consumers evaluate diagonal sums without
    the row points (see ``nystrom.trace_error``)."""
    return spec.scale if spec.name in ("rbf", "matern32") else None


def kernel_diag(x: Array, *, spec: KernelSpec) -> Array:
    """k(x_i, x_i) for each row — O(n) (constant 'scale' for RBF)."""
    if spec.name == "rbf":
        return jnp.full((x.shape[0],), spec.scale, x.dtype)
    if spec.name == "linear":
        return spec.scale * jnp.sum(x * x, axis=-1)
    if spec.name == "poly":
        return spec.scale * (jnp.sum(x * x, axis=-1) + spec.coef0) ** spec.degree
    if spec.name == "matern32":
        return jnp.full((x.shape[0],), spec.scale, x.dtype)
    raise ValueError(f"unknown kernel {spec.name!r}")


def median_heuristic(x: Array, max_points: int = 512) -> Array:
    """sigma = median of pairwise squared distances over a subset (paper §5)."""
    sub = x[:max_points]
    d2 = _sqdist(sub, sub)
    iu = jnp.triu_indices(sub.shape[0], k=1)
    return jnp.median(d2[iu])


def center_gram(K: Array) -> Array:
    """Mean-adjusted kernel matrix K' = (I-1)K(I-1), eq. (1) of the paper."""
    n = K.shape[0]
    one = jnp.full((n, n), 1.0 / n, K.dtype)
    oK = jnp.matmul(one, K, precision=MATMUL_PRECISION)
    return (K - oK - jnp.matmul(K, one, precision=MATMUL_PRECISION)
            + jnp.matmul(oK, one, precision=MATMUL_PRECISION))
