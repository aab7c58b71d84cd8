"""Tiled RBF gram-matrix kernel: G[i,j] = exp(-||x_i - y_j||^2 / sigma).

Decomposed as ||x||^2 + ||y||^2 - 2 x·y so the inner loop is an MXU matmul
over the feature dimension; the norms and the exp() epilogue are fused into
the final reduction step (VPU), so G is written to HBM exactly once and the
distance matrix never materializes.

Used for: streaming kernel rows k(X, x_new) (the per-update O(m d) hot path),
gram blocks for Nyström columns, and the full-K construction in benchmarks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import MATMUL_PRECISION

DEFAULT_BLOCK = 128


def _kernel(x_ref, y_ref, xn_ref, yn_ref, sig_ref, out_ref, acc_ref, *,
            k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # y block arrives as (BJ, BK); contract its dim 1 against x's dim 1 so no
    # in-kernel transpose is required.
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], y_ref[...], (((1,), (1,)), ((), ())),
        precision=MATMUL_PRECISION, preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _done():
        d2 = xn_ref[...] + yn_ref[...] - 2.0 * acc_ref[...]
        d2 = jnp.maximum(d2, 0.0)
        inv_sigma = sig_ref[0, 0]
        out_ref[...] = jnp.exp(-d2 * inv_sigma).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def rbf_gram(x: jax.Array, y: jax.Array, sigma: jax.Array, *,
             block: int = DEFAULT_BLOCK, interpret: bool = False) -> jax.Array:
    """G = exp(-pairwise_sqdist(x, y)/sigma); x: (n,d), y: (m,d)."""
    n, d = x.shape
    m = y.shape[0]
    bi = bj = block
    bk = min(block, max(8, -(-d // 8) * 8))
    np_, mp_, dp_ = -(-n // bi) * bi, -(-m // bj) * bj, -(-d // bk) * bk
    xp = jnp.pad(x, ((0, np_ - n), (0, dp_ - d)))
    yp = jnp.pad(y, ((0, mp_ - m), (0, dp_ - d)))
    xn = jnp.sum(xp * xp, axis=1, keepdims=True)            # (np, 1)
    yn = jnp.sum(yp * yp, axis=1, keepdims=True).T          # (1, mp)
    inv_sigma = (1.0 / sigma).reshape(1, 1).astype(jnp.float32)

    steps = dp_ // bk
    out = pl.pallas_call(
        functools.partial(_kernel, k_steps=steps),
        grid=(np_ // bi, mp_ // bj, steps),
        in_specs=[
            pl.BlockSpec((bi, bk), lambda i, j, k: (i, k)),    # x
            pl.BlockSpec((bj, bk), lambda i, j, k: (j, k)),    # y
            pl.BlockSpec((bi, 1), lambda i, j, k: (i, 0)),     # ||x||^2
            pl.BlockSpec((1, bj), lambda i, j, k: (0, j)),     # ||y||^2
            pl.BlockSpec(memory_space=pltpu.SMEM),             # 1/sigma
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, mp_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bi, bj), jnp.float32)],
        interpret=interpret,
    )(xp, yp, xn, yn, inv_sigma)
    return out[:n, :m]
