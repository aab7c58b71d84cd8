"""Fused Nyström reconstruction kernel: K̃ = B diag(s) B^T.

B = K_{n,m} U is (n, m); s = 1/λ.  The diagonal scaling is fused into the
MXU accumulation (scale the left operand tile in VMEM), so the scaled copy
of B never materializes in HBM — the O(n m^2 / n^2 m) reconstruction used by
the incremental-Nyström stopping rule (paper §4) reads B once and writes K̃
once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import MATMUL_PRECISION

DEFAULT_BLOCK = 128


def _kernel(bi_ref, bj_ref, s_ref, out_ref, acc_ref, *, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    left = bi_ref[...] * s_ref[...]          # fuse diag(s) into the tile
    acc_ref[...] += jax.lax.dot_general(
        left, bj_ref[...], (((1,), (1,)), ((), ())),
        precision=MATMUL_PRECISION, preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _done():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def scaled_gram(b: jax.Array, s: jax.Array, *, block: int = DEFAULT_BLOCK,
                interpret: bool = False) -> jax.Array:
    """K̃[i,j] = sum_k B[i,k] * s[k] * B[j,k]; b: (n, m), s: (m,)."""
    n, m = b.shape
    bi = bj = bk = block
    np_, mp_ = -(-n // bi) * bi, -(-m // bk) * bk
    bp = jnp.pad(b, ((0, np_ - n), (0, mp_ - m)))
    sp = jnp.pad(s, (0, mp_ - m)).reshape(1, mp_).astype(b.dtype)

    steps = mp_ // bk
    out = pl.pallas_call(
        functools.partial(_kernel, k_steps=steps),
        grid=(np_ // bi, np_ // bj, steps),
        in_specs=[
            pl.BlockSpec((bi, bk), lambda i, j, k: (i, k)),   # B rows (i)
            pl.BlockSpec((bj, bk), lambda i, j, k: (j, k)),   # B rows (j)
            pl.BlockSpec((1, bk), lambda i, j, k: (0, k)),    # s
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, np_), b.dtype),
        scratch_shapes=[pltpu.VMEM((bi, bj), jnp.float32)],
        interpret=interpret,
    )(bp, bp, sp)
    return out[:n, :n]
