"""Fused Cauchy-factor eigenvector rotation — the paper's O(m^3) hot spot.

Computes  C = U @ (W * inv[None, :])  where  W[k, j] = zhat[k] / (d[k] - lam[j])
without ever materializing W in HBM: each (BK, BJ) tile of W is generated in
VMEM from three O(M) vectors immediately before the MXU dot-accumulate.

Roofline motivation (TPU v5e, bf16/f32): the naive two-step
(materialize W, then matmul) moves 3·M^2 reads + 2·M^2 writes of HBM traffic;
the fused kernel moves M^2 reads (U) + M^2 writes (C) — a ~2.5× cut on the
memory term, and the VPU divide pipeline overlaps the MXU dot.

Rectangular operands: ``u`` may be a row *block* (R, M) of the full
eigenvector matrix with R != M — the shape the row-sharded distributed
path hands each device ((M/P, M) per mesh slice).  ``row_offset`` carries
the block's first global row index so active-tile pruning works along the
row axis too (see below); R == M with row_offset 0 recovers the original
square kernel exactly.

Active-tile pruning: the incremental-KPCA state is fixed-capacity (M) with
an *active count* m; beyond the active prefix, U is identity, zhat/inv are
zero, and the consumer overwrites the columns anyway.  The grid therefore
prefetches TWO scalar tile counts,

    g_cols = ceil(m / B)                       (column/reduction axes)
    g_rows = ceil(clamp(m - row_offset, 0, R) / B)   (row axis)

and skips every (i, j, k) tile with i >= g_rows or a column/reduction
coordinate >= g_cols: MXU work drops from ceil(R/B)·ceil(M/B)^2 to
ceil(m_rows/B)·ceil(m/B)^2 tiles per update — the flop count the paper's
~8m^3 claim assumes, now preserved at any sharding factor P.  Pruned
output tiles are written as zeros (their true value: rows past m of active
columns are exactly 0 because z is masked beyond the active prefix;
inactive columns are replaced by the caller's own identity columns
downstream).  The original-domain un-flip in ``rankone._solve_factor``
folds the sigma<0 flip's sign into z, so the active region is a prefix —
and this pruning valid — for BOTH sigma signs.

``eigvec_rotate2`` additionally fuses the paper's back-to-back ±sigma
rotations of eq. (2)/(3): C = U @ W1n @ W2n in one pass over U (both W
tiles generated in VMEM), halving HBM round-trips of U per streamed point.
Deflated columns are generated in-kernel as identity columns e_{cid[j]}
(cid carries the inter-update sort permutation), so no intermediate U1 is
ever needed.  The grid walks (i, k) U-tiles with the row axis bounded by
g_rows and every column loop bounded by g_cols, so the fused kernel is
also fully m-pruned at any block shape — g_rows·g_cols² MXU tiles per
factor and only the active rows × active columns of U fetched.

Tiling: (BI, BJ) output tiles, reduction over K in the innermost grid axis;
MXU-aligned 128×128×128 blocks by default.  Vectors are carried as (M, 1) /
(1, M) so no in-kernel transposes are needed (lane/sublane friendly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import MATMUL_PRECISION

DEFAULT_BLOCK = 128
NPROJ = 8    # projected column count of ``eigvec_project`` (v padded to 8)


def _tile_counts(num_active, row_offset, R: int, M: int, block: int,
                 steps_r: int, steps_c: int) -> jax.Array:
    """(2,) int32 scalar-prefetch vector [g_rows, g_cols].

    g_cols bounds the column AND reduction axes (both indexed by the
    factor's active prefix m); g_rows bounds the row axis of the (R, M)
    block whose first global row is ``row_offset``.
    """
    if num_active is None:
        return jnp.asarray([steps_r, steps_c], jnp.int32)
    na = jnp.asarray(num_active, jnp.int32)
    g_cols = jnp.minimum(-(-na // block), steps_c)
    r0 = (jnp.zeros((), jnp.int32) if row_offset is None
          else jnp.asarray(row_offset, jnp.int32))
    rows_active = jnp.clip(na - r0, 0, R)
    g_rows = jnp.minimum(-(-rows_active // block), steps_r)
    return jnp.stack([g_rows, g_cols]).astype(jnp.int32)


def _clamp(t, lim):
    # Redirect pruned-tile block loads to tile 0: the iteration is skipped
    # anyway, so don't spend HBM bandwidth on its operands.
    return jnp.minimum(t, jnp.maximum(lim - 1, 0))


def _kernel(g_ref, u_ref, z_ref, d_ref, lam_ref, tau_ref, inv_ref, out_ref,
            acc_ref, *, k_steps: int):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    gr, gc = g_ref[0], g_ref[1]
    active = (i < gr) & (j < gc)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(active & (k < gc))
    def _acc():
        # Generate the W tile in VMEM: (BK, 1) vectors against (1, BJ).
        zcol = z_ref[...]            # (BK, 1)
        dcol = d_ref[...]            # (BK, 1)
        lamrow = lam_ref[...]        # (1, BJ)
        # (BK, BJ) Cauchy tile, never hits HBM; the root is lam + tau.
        # A distance below the smallest normal number is flushed to zero
        # on a TPU; nudge it as ``_w_tile`` does, or 0/0 poisons the tile.
        den = (dcol - lamrow) - tau_ref[...]
        tiny = jnp.finfo(den.dtype).tiny
        den = jnp.where(jnp.abs(den) < tiny,
                        jnp.where(den < 0, -tiny, tiny), den)
        w = zcol / den
        acc_ref[...] += jnp.dot(u_ref[...], w, precision=MATMUL_PRECISION,
                                preferred_element_type=acc_ref.dtype)

    @pl.when(k == k_steps - 1)
    def _done():
        # Pruned tiles were never accumulated: acc is still zero there, the
        # correct value for rows/columns beyond the active prefix.
        out_ref[...] = (acc_ref[...] * inv_ref[...]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def eigvec_rotate(u: jax.Array, zhat: jax.Array, d: jax.Array,
                  lam: jax.Array, inv: jax.Array,
                  num_active: jax.Array | None = None,
                  row_offset: jax.Array | None = None,
                  tau: jax.Array | None = None, *,
                  block: int = DEFAULT_BLOCK,
                  interpret: bool = False) -> jax.Array:
    """C[i, j] = sum_k U[i,k] * zhat[k]/((d[k]-lam[j])-tau[j]) * inv[j].

    The root of column j is lam[j] + tau[j]: ``rankone`` passes the pole
    nearer to the root as lam and the offset as tau (default 0), so the
    distance keeps its relative accuracy however close the root is.

    u: (R, M) — a row block of the eigenvector matrix (R == M for the
    single-device square case); zhat, d, lam, inv: (M,).  Both dims are
    padded internally to a multiple of ``block``; padded columns use
    lam=1e30 / d=2e30 so generated W entries are exactly 0 (no NaNs enter
    the accumulator).

    ``num_active`` (traced scalar, optional): active count m.  Column and
    reduction tiles beyond ceil(m/block) are skipped; row tiles beyond
    ceil(clamp(m - row_offset, 0, R)/block) likewise (``row_offset`` is
    the block's first global row, default 0).  Pruned output is written
    as zero — callers must treat columns >= m as garbage-to-overwrite
    (rankone does) while pruned *rows* of active columns are exactly 0 by
    the padding contract, so zeros there are the true values.
    """
    R, M = u.shape
    Rp = -(-R // block) * block
    Mp = -(-M // block) * block
    pad_r, pad_c = Rp - R, Mp - M
    dtype = u.dtype
    if pad_r or pad_c:
        u = jnp.pad(u, ((0, pad_r), (0, pad_c)))
    if tau is None:
        tau = jnp.zeros_like(lam)
    if pad_c:
        zhat = jnp.pad(zhat, (0, pad_c))
        d = jnp.pad(d, (0, pad_c), constant_values=2e30)
        lam = jnp.pad(lam, (0, pad_c), constant_values=1e30)
        tau = jnp.pad(tau, (0, pad_c))
        inv = jnp.pad(inv, (0, pad_c))
    zcol = zhat.reshape(Mp, 1).astype(dtype)
    dcol = d.reshape(Mp, 1).astype(dtype)
    lamrow = lam.reshape(1, Mp).astype(dtype)
    taurow = tau.reshape(1, Mp).astype(dtype)
    invrow = inv.reshape(1, Mp).astype(dtype)

    steps_r = Rp // block
    steps = Mp // block
    g = _tile_counts(num_active, row_offset, R, M, block, steps_r, steps)
    # Accumulate in f32 for <=32-bit operands, f64 for f64 states (the
    # precise/x64 numerics tier needs the rotation itself at 1e-12).
    acc_dtype = jnp.promote_types(dtype, jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(steps_r, steps, steps),
        in_specs=[
            pl.BlockSpec((block, block),
                         lambda i, j, k, g: (_clamp(i, g[0]),
                                             _clamp(k, g[1]))),
            pl.BlockSpec((block, 1), lambda i, j, k, g: (_clamp(k, g[1]), 0)),
            pl.BlockSpec((block, 1), lambda i, j, k, g: (_clamp(k, g[1]), 0)),
            pl.BlockSpec((1, block), lambda i, j, k, g: (0, _clamp(j, g[1]))),
            pl.BlockSpec((1, block), lambda i, j, k, g: (0, _clamp(j, g[1]))),
            pl.BlockSpec((1, block), lambda i, j, k, g: (0, _clamp(j, g[1]))),
        ],
        out_specs=pl.BlockSpec((block, block), lambda i, j, k, g: (i, j)),
        scratch_shapes=[pltpu.VMEM((block, block), acc_dtype)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, k_steps=steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Rp, Mp), dtype),
        interpret=interpret,
    )(g, u, zcol, dcol, lamrow, taurow, invrow)
    return out[:R, :M]


def _proj_kernel(g_ref, u_ref, v_ref, out_ref, acc_ref, *, r_steps: int,
                 block: int):
    """P-tile accumulate for ``eigvec_project``: out[j] = Σ_i Uᵀ[j,i] V[i]."""
    j, i = pl.program_id(0), pl.program_id(1)
    gr, gc = g_ref[0], g_ref[1]
    m, r0 = g_ref[2], g_ref[3]

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((i < gr) & (j < gc))
    def _acc():
        rows = (r0 + i * block
                + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0))
        v = jnp.where(rows < m, v_ref[...].astype(acc_ref.dtype), 0.0)
        acc_ref[...] += jax.lax.dot_general(
            u_ref[...].astype(acc_ref.dtype), v, (((0,), (0,)), ((), ())),
            precision=MATMUL_PRECISION, preferred_element_type=acc_ref.dtype)

    @pl.when(i == r_steps - 1)
    def _done():
        # Pruned (j >= gc) output tiles were never accumulated: zero is
        # their true value — inactive U columns are identity columns whose
        # single 1 sits on a masked row (>= m) of V.
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def eigvec_project(u: jax.Array, v: jax.Array,
                   num_active: jax.Array | None = None,
                   row_offset: jax.Array | None = None, *,
                   block: int = DEFAULT_BLOCK,
                   interpret: bool = False) -> jax.Array:
    """P = Uᵀ V with the row mask and active-tile pruning of the rotation
    kernels: the post-rotation projection pass of Algorithm 2's second
    ±sigma pair (and any other Uᵀv the caller owes in the CURRENT basis).

    u: (R, M) eigenvector row block (first global row ``row_offset``);
    v: (R, C) columns to project, C <= NPROJ; rows >= ``num_active``
    (global index) are masked to zero in-kernel, so the caller may pass
    unmasked vectors.  Returns (M, C).  Reduction (row) tiles stop at
    ceil(clamp(m - row_offset, 0, R)/block) and output (column-of-U) tiles
    at ceil(m/block); pruned output rows are exact zeros — the true value,
    because inactive U columns are identity columns supported on masked
    rows.  Row-sharded callers psum the (M, C) partials over shards.
    """
    R, M = u.shape
    C = v.shape[1]
    if C > NPROJ:
        raise ValueError(f"eigvec_project supports <= {NPROJ} columns, "
                         f"got {C}")
    Rp = -(-R // block) * block
    Mp = -(-M // block) * block
    pad_r, pad_c = Rp - R, Mp - M
    dtype = u.dtype
    if pad_r or pad_c:
        u = jnp.pad(u, ((0, pad_r), (0, pad_c)))
    if pad_r or C < NPROJ:
        v = jnp.pad(v, ((0, pad_r), (0, NPROJ - C)))
    v = v.astype(dtype)

    steps_r = Rp // block
    steps_c = Mp // block
    g2 = _tile_counts(num_active, row_offset, R, M, block, steps_r, steps_c)
    m_eff = (jnp.asarray(M, jnp.int32) if num_active is None
             else jnp.asarray(num_active, jnp.int32))
    r0 = (jnp.zeros((), jnp.int32) if row_offset is None
          else jnp.asarray(row_offset, jnp.int32))
    g = jnp.concatenate([g2, m_eff[None], r0[None]]).astype(jnp.int32)
    acc_dtype = jnp.promote_types(dtype, jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(steps_c, steps_r),
        in_specs=[
            pl.BlockSpec((block, block),
                         lambda j, i, g: (_clamp(i, g[0]), _clamp(j, g[1]))),
            pl.BlockSpec((block, NPROJ),
                         lambda j, i, g: (_clamp(i, g[0]), 0)),
        ],
        out_specs=pl.BlockSpec((block, NPROJ), lambda j, i, g: (j, 0)),
        scratch_shapes=[pltpu.VMEM((block, NPROJ), acc_dtype)],
    )
    out = pl.pallas_call(
        functools.partial(_proj_kernel, r_steps=steps_r, block=block),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, NPROJ), dtype),
        interpret=interpret,
    )(g, u, v)
    return out[:M, :C]


def _w_tile(z_ref, d_ref, lam_ref, tau_ref, inv_ref, defl_ref, cid_ref, k,
            l, *, block: int, tiny: float):
    """(block, block) tile (k, l) of a normalized Cauchy factor.

    w[r, c] = defl[c] ? (row_r == cid[c])
                      : z[r] * inv[c] / ((d[r] - lam[c]) - tau[c])
    with r/c the in-tile offsets of global rows k·B+r, columns l·B+c.
    (W's row space is the eigenvector COLUMN index, so this is independent
    of any row-blocking of U.)
    """
    rs = pl.dslice(k * block, block)
    cs = pl.dslice(l * block, block)
    z = z_ref[rs, :]                     # (block, 1)
    d = d_ref[rs, :]                     # (block, 1)
    lam = lam_ref[:, cs]                 # (1, block)
    tau = tau_ref[:, cs]                 # (1, block)
    inv = inv_ref[:, cs]                 # (1, block)
    defl = defl_ref[:, cs]               # (1, block) float 0/1
    cid = cid_ref[:, cs]                 # (1, block) int32
    den = (d - lam) - tau
    den = jnp.where(jnp.abs(den) < tiny,
                    jnp.where(den < 0, -tiny, tiny), den)
    w = z * inv / den
    rows = k * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    return jnp.where(defl > 0, (rows == cid).astype(w.dtype), w)


def _kernel2(g_ref, u_ref,
             z1_ref, d1_ref, lam1_ref, tau1_ref, inv1_ref, defl1_ref,
             cid1_ref,
             z2_ref, d2_ref, lam2_ref, tau2_ref, inv2_ref, defl2_ref,
             cid2_ref,
             out_ref, t_ref, *, k_steps: int, block: int, tiny: float):
    i, k = pl.program_id(0), pl.program_id(1)
    gr, gc = g_ref[0], g_ref[1]

    @pl.when(k == 0)
    def _init():
        t_ref[...] = jnp.zeros_like(t_ref)

    # Accumulate T = U_row @ W1n one (i, k) U-tile at a time, so both the
    # MXU work and the U HBM fetches stop at the active tile ranges.
    @pl.when((i < gr) & (k < gc))
    def _acc():
        u_blk = u_ref[...]                               # (block, block)

        def body1(l, carry):
            w1 = _w_tile(z1_ref, d1_ref, lam1_ref, tau1_ref, inv1_ref,
                         defl1_ref, cid1_ref, k, l, block=block, tiny=tiny)
            sl = pl.dslice(l * block, block)
            t_ref[:, sl] += jnp.dot(u_blk, w1, precision=MATMUL_PRECISION,
                                    preferred_element_type=t_ref.dtype)
            return carry

        jax.lax.fori_loop(0, gc, body1, 0)

    # Second factor once T is complete.  Pruned column slabs (and pruned
    # row blocks entirely) are zero — correct for the padding contract.
    @pl.when(k == k_steps - 1)
    def _emit():
        out_ref[...] = jnp.zeros_like(out_ref)

        @pl.when(i < gr)
        def _second():
            def body2(j, carry):
                def inner(l, acc):
                    w2 = _w_tile(z2_ref, d2_ref, lam2_ref, tau2_ref,
                                 inv2_ref, defl2_ref, cid2_ref, l, j,
                                 block=block, tiny=tiny)
                    t_blk = t_ref[:, pl.dslice(l * block, block)]
                    return acc + jnp.dot(t_blk, w2.astype(t_ref.dtype),
                                         precision=MATMUL_PRECISION,
                                         preferred_element_type=t_ref.dtype)

                acc0 = jnp.zeros((block, block), t_ref.dtype)
                out_ref[:, pl.dslice(j * block, block)] = (
                    jax.lax.fori_loop(0, gc, inner, acc0).astype(
                        out_ref.dtype))
                return carry

            jax.lax.fori_loop(0, gc, body2, 0)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def eigvec_rotate2(u: jax.Array,
                   z1: jax.Array, d1: jax.Array, lam1: jax.Array,
                   inv1: jax.Array, defl1: jax.Array, cid1: jax.Array,
                   z2: jax.Array, d2: jax.Array, lam2: jax.Array,
                   inv2: jax.Array, defl2: jax.Array, cid2: jax.Array,
                   num_active: jax.Array | None = None,
                   row_offset: jax.Array | None = None,
                   tau1: jax.Array | None = None,
                   tau2: jax.Array | None = None, *,
                   block: int = DEFAULT_BLOCK,
                   interpret: bool = False) -> jax.Array:
    """Fused double rotation  C = U @ W1n @ W2n  in one pass over U.

    Each factor is W[k, j] = z[k]·inv[j]/((d[k]-lam[j])-tau[j]) (root
    lam + tau, tau defaulting to 0; see ``eigvec_rotate``), except deflated
    columns (defl[j] != 0) which are identity columns e_{cid[j]} — cid
    carries the sort permutation applied between the two updates.  ``u``
    may be a rectangular (R, M) row block (``row_offset`` = first global
    row); the grid walks (i, k) U-tiles bounded by (g_rows, g_cols); the
    intermediate T = U_row @ W1n lives only in VMEM scratch (never HBM).
    VMEM footprint per program is the (B, M) T row plus (B, B) tiles
    ≈ B·M·4 bytes.
    """
    R, M = u.shape
    Rp = -(-R // block) * block
    Mp = -(-M // block) * block
    pad_r, pad_c = Rp - R, Mp - M
    dtype = u.dtype
    if pad_r or pad_c:
        u = jnp.pad(u, ((0, pad_r), (0, pad_c)))
    tau1 = jnp.zeros_like(lam1) if tau1 is None else tau1
    tau2 = jnp.zeros_like(lam2) if tau2 is None else tau2
    if pad_c:
        z1, z2 = (jnp.pad(v, (0, pad_c)) for v in (z1, z2))
        tau1, tau2 = (jnp.pad(v, (0, pad_c)) for v in (tau1, tau2))
        d1, d2 = (jnp.pad(v, (0, pad_c), constant_values=2e30)
                  for v in (d1, d2))
        lam1, lam2 = (jnp.pad(v, (0, pad_c), constant_values=1e30)
                      for v in (lam1, lam2))
        inv1, inv2 = (jnp.pad(v, (0, pad_c)) for v in (inv1, inv2))
        defl1, defl2 = (jnp.pad(v, (0, pad_c)) for v in (defl1, defl2))
        cid1, cid2 = (jnp.pad(v, (0, pad_c), constant_values=Mp)
                      for v in (cid1, cid2))

    def col(v):
        return v.reshape(Mp, 1).astype(dtype)

    def row(v, as_dtype=None):
        return v.reshape(1, Mp).astype(as_dtype or dtype)

    steps_r = Rp // block
    steps = Mp // block
    g = _tile_counts(num_active, row_offset, R, M, block, steps_r, steps)
    acc_dtype = jnp.promote_types(dtype, jnp.float32)

    vec_specs = [
        pl.BlockSpec((Mp, 1), lambda i, k, g: (0, 0)),   # z
        pl.BlockSpec((Mp, 1), lambda i, k, g: (0, 0)),   # d
        pl.BlockSpec((1, Mp), lambda i, k, g: (0, 0)),   # lam
        pl.BlockSpec((1, Mp), lambda i, k, g: (0, 0)),   # tau
        pl.BlockSpec((1, Mp), lambda i, k, g: (0, 0)),   # inv
        pl.BlockSpec((1, Mp), lambda i, k, g: (0, 0)),   # defl
        pl.BlockSpec((1, Mp), lambda i, k, g: (0, 0)),   # cid
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(steps_r, steps),
        in_specs=[pl.BlockSpec(
            (block, block),
            lambda i, k, g: (_clamp(i, g[0]), _clamp(k, g[1])))]
        + vec_specs + vec_specs,
        out_specs=pl.BlockSpec((block, Mp), lambda i, k, g: (i, 0)),
        scratch_shapes=[pltpu.VMEM((block, Mp), acc_dtype)],
    )
    tiny = float(jnp.finfo(dtype).tiny)
    out = pl.pallas_call(
        functools.partial(_kernel2, k_steps=steps, block=block, tiny=tiny),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Rp, Mp), dtype),
        interpret=interpret,
    )(g, u,
      col(z1), col(d1), row(lam1), row(tau1), row(inv1), row(defl1),
      row(cid1, jnp.int32),
      col(z2), col(d2), row(lam2), row(tau2), row(inv2), row(defl2),
      row(cid2, jnp.int32))
    return out[:R, :M]
