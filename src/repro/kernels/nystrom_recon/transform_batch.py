"""Fused batched transform: query kernel rows + component projection.

The unfused transform materializes the full (Q, M) query gram K_q in HBM,
re-reads it for the projection K_q @ S (S = U_active / sqrt(lam)), and —
on the mean-adjusted path — re-reads it a third time for the per-query
row sums.  This kernel produces each (block, block) K_q tile in VMEM from
the stored points (same squared-distance + ``kernel_epilogue`` recipe as
the fused ingest kernel ``rbf_gram/krow_fused.py``) and immediately
contracts it against the matching S row tile, accumulating the row sums
in the same pass — K_q never makes a trip to HBM, X and S are read once
per query tile, and the outputs (Y, rowsum) are everything the adjusted
centering needs as an affine post-correction.

Active-prefix pruning: m-tiles beyond ceil(m / block) are skipped via the
scalar-prefetched tile count (the masked K_q columns >= m are zero, and S
rows >= m are zero for active components — the state invariant), so the
pass costs O(Q·m·(d + C)), not O(Q·M·(d + C)).

Nyström query features ride the same kernel: S = U diag(pinv-ish scaling)
is just a different projection matrix, and the reconstruction
K̃_qq = Y diag(lam) Yᵀ then reuses the ``scaled_gram`` tile recipe.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import kernels_fn as kf
from repro.core.precision import MATMUL_PRECISION
from repro.kernels.rbf_gram.krow_fused import _clamp, kernel_epilogue

DEFAULT_BLOCK = 128


def _kernel(g_ref, xq_ref, x_ref, xn_ref, qn_ref, s_ref, y_ref, rs_ref,
            acc_ref, rs_acc_ref, *, m_steps: int, block: int, name: str,
            sigma: float, scale: float):
    k = pl.program_id(1)
    gc, m = g_ref[0], g_ref[1]

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        rs_acc_ref[...] = jnp.zeros_like(rs_acc_ref)

    @pl.when(k < gc)
    def _acc():
        dot = jax.lax.dot_general(
            xq_ref[...], x_ref[...], (((1,), (1,)), ((), ())),
            precision=MATMUL_PRECISION, preferred_element_type=acc_ref.dtype)
        d2 = jnp.maximum(
            qn_ref[...] + xn_ref[...] - 2.0 * dot.astype(acc_ref.dtype), 0.0)
        kq = kernel_epilogue(d2, name=name, sigma=sigma, scale=scale)
        cols = (k * block
                + jax.lax.broadcasted_iota(jnp.int32, (block, block), 1))
        kqm = jnp.where(cols < m, kq, 0.0)
        acc_ref[...] += jax.lax.dot_general(
            kqm, s_ref[...].astype(acc_ref.dtype), (((1,), (0,)), ((), ())),
            precision=MATMUL_PRECISION, preferred_element_type=acc_ref.dtype)
        rs_acc_ref[...] += jnp.sum(kqm, axis=1, keepdims=True)

    @pl.when(k == m_steps - 1)
    def _done():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)
        rs_ref[...] = rs_acc_ref[...].astype(rs_ref.dtype)


@functools.partial(jax.jit, static_argnames=("spec", "block", "interpret"))
def transform_project(xq: jax.Array, x: jax.Array, s: jax.Array,
                      num_active: jax.Array, *, spec: kf.KernelSpec,
                      block: int = DEFAULT_BLOCK, interpret: bool = False
                      ) -> tuple[jax.Array, jax.Array]:
    """(Y, rowsum): Y = K_q_masked @ s and rowsum = K_q_masked @ 1, fused.

    xq: (Q, d) query points; x: (M, d) stored points; s: (M, C) projection
    matrix (component scaling already folded in).  K_q[i, j] =
    k(xq[i], x[j]) zeroed on columns >= num_active, never materialized.
    """
    Q, d = xq.shape
    M = x.shape[0]
    C = s.shape[1]
    dtype = s.dtype
    Qp = -(-Q // block) * block
    Mp = -(-M // block) * block
    dp = -(-d // 8) * 8
    Cp = max(8, -(-C // 8) * 8)

    m = jnp.asarray(num_active, jnp.int32)
    xqp = jnp.pad(xq.astype(dtype), ((0, Qp - Q), (0, dp - d)))
    xp = jnp.pad(x.astype(dtype), ((0, Mp - M), (0, dp - d)))
    qn = jnp.sum(xqp * xqp, axis=1, keepdims=True)           # (Qp, 1)
    xn = jnp.sum(xp * xp, axis=1).reshape(1, Mp)             # (1, Mp)
    sp = jnp.pad(s, ((0, Mp - M), (0, Cp - C)))

    steps_m = Mp // block
    g_cols = jnp.minimum(-(-m // block), steps_m)
    g = jnp.stack([g_cols, m]).astype(jnp.int32)
    acc_dtype = jnp.promote_types(dtype, jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Qp // block, steps_m),
        in_specs=[
            pl.BlockSpec((block, dp), lambda i, k, g: (i, 0)),       # xq
            pl.BlockSpec((block, dp),
                         lambda i, k, g: (_clamp(k, g[0]), 0)),      # x
            pl.BlockSpec((1, block),
                         lambda i, k, g: (0, _clamp(k, g[0]))),      # ||x||^2
            pl.BlockSpec((block, 1), lambda i, k, g: (i, 0)),        # ||xq||^2
            pl.BlockSpec((block, Cp),
                         lambda i, k, g: (_clamp(k, g[0]), 0)),      # s
        ],
        out_specs=[
            pl.BlockSpec((block, Cp), lambda i, k, g: (i, 0)),       # Y
            pl.BlockSpec((block, 1), lambda i, k, g: (i, 0)),        # rowsum
        ],
        scratch_shapes=[pltpu.VMEM((block, Cp), acc_dtype),
                        pltpu.VMEM((block, 1), acc_dtype)],
    )
    y, rs = pl.pallas_call(
        functools.partial(_kernel, m_steps=steps_m, block=block,
                          name=spec.name, sigma=float(spec.sigma),
                          scale=float(spec.scale)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((Qp, Cp), dtype),
                   jax.ShapeDtypeStruct((Qp, 1), dtype)],
        interpret=interpret,
    )(g, xqp, xp, xn, qn, sp)
    return y[:Q, :C], rs[:Q, 0]
