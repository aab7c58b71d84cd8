"""Fused kernel-row producer + eigenbasis projection: the ingest prologue.

Every streamed point consumes a kernel row a = [k(x_i, x_new)] and its
projection P = U^T [a | aux] (aux carries Algorithm-2 side vectors such as
the masked ones vector and the row-sum vector K1).  The unfused pipeline
pays three HBM round-trips — write a, re-read a, re-read U — before the
rotation kernels even start.  This kernel produces the row tile-by-tile in
VMEM from the stored points X and immediately contracts it against the
matching U row tile, so the kernel row never makes a standalone trip to
HBM and U is read exactly once for the whole prologue.

Supports the rectangular (R, M) row-block form of ``eigvec_update``: ``u``
and ``x`` may cover only rows [row_offset, row_offset + R) of the global
state, so the row-sharded distributed path runs the same kernel per shard
and psums the partial P.  Active-prefix pruning follows the same
``g_rows``/``g_cols`` scalar-prefetch discipline as the rotation kernels:
U tiles beyond the active prefix are never fetched, and pruned P tiles are
zero — their true value, because the masked row and masked aux vanish on
rows >= m and inactive U columns are identity columns living entirely in
that masked region.

Kernels: RBF and Matérn-3/2 (the stationary kernels of ``kernels_fn``);
the epilogues match ``kernels_fn.gram_block`` term-for-term so the fused
path is numerically the reference path.  The KernelSpec is jit-static, so
sigma/scale are compile-time constants inside the kernel body.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import kernels_fn as kf
from repro.core.precision import MATMUL_PRECISION

DEFAULT_BLOCK = 128
NAUX = 8          # projected column count: kernel row + up to 7 aux columns

PALLAS_KERNELS = ("rbf", "matern32")


def _clamp(t, lim):
    # Redirect pruned-tile block loads to tile 0 (iteration skipped anyway).
    return jnp.minimum(t, jnp.maximum(lim - 1, 0))


def kernel_epilogue(d2, *, name: str, sigma: float, scale: float):
    """Squared-distance -> kernel-value epilogue, shared by every fused
    kernel tile (k-row ingest here, batched transform in nystrom_recon).
    Matches ``kernels_fn`` term-for-term."""
    if name == "rbf":
        return scale * jnp.exp(-d2 / sigma)
    if name == "matern32":
        aa = jnp.sqrt(3.0) * jnp.sqrt(d2 + 1e-30) / sigma
        return scale * (1.0 + aa) * jnp.exp(-aa)
    raise ValueError(f"no fused epilogue for kernel {name!r}")


def _krow_tile(x_blk, xn_blk, xq, *, name: str, sigma: float, scale: float):
    """(block, 1) kernel-row tile k(x_blk, xq) — matches kernels_fn exactly."""
    qn = jnp.sum(xq * xq)
    dot = jax.lax.dot_general(
        x_blk, xq, (((1,), (1,)), ((), ())), precision=MATMUL_PRECISION,
        preferred_element_type=jnp.promote_types(x_blk.dtype, jnp.float32))
    d2 = jnp.maximum(xn_blk + qn - 2.0 * dot.astype(xn_blk.dtype), 0.0)
    return kernel_epilogue(d2, name=name, sigma=sigma, scale=scale)


def _kernel(g_ref, u_ref, x_ref, xn_ref, xq_ref, aux_ref, a_ref, p_ref,
            acc_ref, *, r_steps: int, block: int, name: str, sigma: float,
            scale: float):
    j, i = pl.program_id(0), pl.program_id(1)
    gr, gc = g_ref[0], g_ref[1]
    m, r0 = g_ref[2], g_ref[3]

    kr = _krow_tile(x_ref[...], xn_ref[...], xq_ref[...],
                    name=name, sigma=sigma, scale=scale)
    rows = (r0 + i * block
            + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0))
    krm = jnp.where(rows < m, kr, 0.0).astype(a_ref.dtype)
    # Row tiles beyond g_rows load clamped (wrong) operands, but every such
    # row is >= m, so the mask writes the true value (zero) regardless.
    a_ref[...] = krm

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((i < gr) & (j < gc))
    def _acc():
        cols = jax.lax.broadcasted_iota(jnp.int32, (block, NAUX), 1)
        v = jnp.where(cols == 0, krm.astype(acc_ref.dtype),
                      aux_ref[...].astype(acc_ref.dtype))
        acc_ref[...] += jax.lax.dot_general(
            u_ref[...].astype(acc_ref.dtype), v, (((0,), (0,)), ((), ())),
            precision=MATMUL_PRECISION, preferred_element_type=acc_ref.dtype)

    @pl.when(i == r_steps - 1)
    def _done():
        # Pruned (j >= gc) tiles were never accumulated: zero is their true
        # value — inactive U columns are identity columns whose single 1
        # lands on a masked row of [a | aux].
        p_ref[...] = acc_ref[...].astype(p_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("spec", "block", "interpret"))
def krow_project(u: jax.Array, x: jax.Array, x_new: jax.Array,
                 aux: jax.Array, num_active: jax.Array,
                 row_offset: jax.Array | None = None, *,
                 spec: kf.KernelSpec, block: int = DEFAULT_BLOCK,
                 interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """(a, P): masked kernel row + its eigenbasis projection, one pass.

    u:   (R, M) eigenvector row block (R == M, row_offset 0 single-device)
    x:   (R, d) stored points for those rows
    aux: (R, naux) extra columns to project alongside the row (naux <= 7)

    Returns a: (R,) = k(x, x_new) zeroed on global rows >= num_active, and
    P: (M, 1 + naux) = u^T [a | aux_masked].  Sharded callers psum P.
    """
    R, M = u.shape
    d = x.shape[1]
    naux = aux.shape[1]
    if naux + 1 > NAUX:
        raise ValueError(f"at most {NAUX - 1} aux columns, got {naux}")
    dtype = u.dtype
    Rp = -(-R // block) * block
    Mp = -(-M // block) * block
    dp = -(-d // 8) * 8

    m = jnp.asarray(num_active, jnp.int32)
    r0 = (jnp.zeros((), jnp.int32) if row_offset is None
          else jnp.asarray(row_offset, jnp.int32))
    rows = r0 + jnp.arange(R, dtype=jnp.int32)
    auxm = jnp.where(rows[:, None] < m, aux.astype(dtype), 0.0)

    up = jnp.pad(u, ((0, Rp - R), (0, Mp - M)))
    xp = jnp.pad(x.astype(dtype), ((0, Rp - R), (0, dp - d)))
    xn = jnp.sum(xp * xp, axis=1, keepdims=True)              # (Rp, 1)
    xq = jnp.pad(x_new.astype(dtype), (0, dp - d)).reshape(1, dp)
    auxp = jnp.zeros((Rp, NAUX), dtype).at[:R, 1:1 + naux].set(auxm)

    steps_r = Rp // block
    steps_c = Mp // block
    g_cols = jnp.minimum(-(-m // block), steps_c)
    g_rows = jnp.minimum(-(-jnp.clip(m - r0, 0, R) // block), steps_r)
    g = jnp.stack([g_rows, g_cols, m, r0]).astype(jnp.int32)
    acc_dtype = jnp.promote_types(dtype, jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(steps_c, steps_r),
        in_specs=[
            pl.BlockSpec((block, block),
                         lambda j, i, g: (_clamp(i, g[0]),
                                          _clamp(j, g[1]))),    # u
            pl.BlockSpec((block, dp),
                         lambda j, i, g: (_clamp(i, g[0]), 0)),  # x
            pl.BlockSpec((block, 1),
                         lambda j, i, g: (_clamp(i, g[0]), 0)),  # ||x||^2
            pl.BlockSpec((1, dp), lambda j, i, g: (0, 0)),      # x_new
            pl.BlockSpec((block, NAUX),
                         lambda j, i, g: (_clamp(i, g[0]), 0)),  # aux
        ],
        out_specs=[
            pl.BlockSpec((block, 1), lambda j, i, g: (i, 0)),    # a
            pl.BlockSpec((block, NAUX), lambda j, i, g: (j, 0)),  # P
        ],
        scratch_shapes=[pltpu.VMEM((block, NAUX), acc_dtype)],
    )
    a, P = pl.pallas_call(
        functools.partial(_kernel, r_steps=steps_r, block=block,
                          name=spec.name, sigma=float(spec.sigma),
                          scale=float(spec.scale)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((Rp, 1), dtype),
                   jax.ShapeDtypeStruct((Mp, NAUX), dtype)],
        interpret=interpret,
    )(g, up, xp, xn, xq, auxp)
    return a[:R, 0], P[:M, :1 + naux]
