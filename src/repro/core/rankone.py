"""Rank-one updates to the symmetric eigendecomposition (paper §3.2).

Given A = U diag(d) U^T and a symmetric perturbation A + sigma * v v^T, the
updated eigenvalues are the roots of the secular equation (Golub 1973)

    w(t) = 1 + sigma * sum_i z_i^2 / (d_i - t),        z = U^T v

and the updated eigenvectors are U @ W with W[:, j] ∝ z / (d - t_j)
(Bunch, Nielsen & Sorensen 1978).  Two eigenvector variants are provided:

* ``method="bns"``  — paper-faithful: use z directly (Bunch et al. 1978).
* ``method="gu"``   — beyond-paper stability upgrade: recompute ẑ from the
  computed roots via the Gu & Eisenstat (1994) identity, which restores
  numerical orthogonality of the updated eigenvectors (the paper cites this
  line of work as a possible improvement; we implement it).

Design for TPUs / jit:

* **Fixed capacity M with an active count m.**  All arrays are padded to a
  static capacity; inactive eigenpairs are kept as exact identity pairs
  (U[:, j] = e_j) with *sentinel* eigenvalues placed strictly above the
  active spectrum.  One XLA compilation then serves an entire stream of
  updates — no per-step retracing, and static shapes as TPUs require.
* **Vectorized fixed-iteration bisection** for the secular equation: all M
  roots are bracketed by the interlacing bounds (paper eq. 5) and refined
  branch-free in parallel — O(iters · M^2) VPU work.
* The O(M^3) eigenvector rotation U @ W is the compute hot spot; W is a
  Cauchy-like matrix generated from three O(M) vectors, so the matmul is
  performed by a fused Pallas kernel (``repro.kernels.eigvec_update``) that
  builds W tiles in VMEM on the fly (set ``matmul="pallas"``).
* sigma < 0 is reduced to sigma > 0 via the flip identity
  ``eig(D + s zz^T) = -rev(eig(-rev(D) + |s| rev(z)rev(z)^T))``.

Stage names (``jax.named_scope``, stable: the benchmark's trace
reduction attributes device time by them): ``kpca.krow`` the projection
z = Uᵀv of the update vector (with the kernel row its ingest builds),
``kpca.solve`` sentinels, cluster merge, deflation, secular bisection,
Gu–Eisenstat ẑ and the Cauchy norms, ``kpca.rotate`` the eigenvector
rotation U @ W and the column permutations that re-sort U.
"""
from __future__ import annotations

from functools import partial
from typing import Literal, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.precision import MATMUL_PRECISION

Array = jax.Array

# Margin multiplier used when regenerating sentinel eigenvalues.
_SENTINEL_GAP = 1.0


def _eps_for(dtype) -> float:
    return float(jnp.finfo(dtype).eps)


def active_mask(M: int, m: Array) -> Array:
    return jnp.arange(M) < m


def sentinelize(d: Array, m: Array, room: Array) -> Array:
    """Place inactive eigenvalues strictly above the active spectrum.

    ``room`` is an upper bound on how far the top active root can travel
    (sigma * ||z||^2 for sigma > 0, else 0).  Sentinels are spaced by 1 so
    bisection intervals in the inactive region are well conditioned.
    """
    M = d.shape[0]
    mask = active_mask(M, m)
    top = jnp.max(jnp.where(mask, d, -jnp.inf))
    top = jnp.where(jnp.isfinite(top), top, 0.0)  # m == 0 corner
    base = top + jnp.abs(room) + _SENTINEL_GAP
    idx = jnp.arange(M, dtype=d.dtype)
    sent = base + _SENTINEL_GAP * (idx - m.astype(d.dtype))
    return jnp.where(mask, d, sent)


def _safe_den(den: Array) -> Array:
    """Pole distance with exact zeros (only possible for a root pinned on
    its own deflated pole) nudged to the smallest normal number, sign
    kept — a relative guard, so a genuinely tiny distance is kept."""
    tiny = jnp.finfo(den.dtype).tiny
    return jnp.where(jnp.abs(den) < tiny, jnp.where(den < 0, -tiny, tiny),
                     den)


def _log(r: Array) -> Array:
    """Natural log of r > 0 to working accuracy on every backend.

    The TPU's f32 ``log`` is off by hundreds of ulps (thousands near
    r = 1), and the Gu–Eisenstat product below sums M of them, so the
    eigenvectors would lose orthogonality at that rate.  Here only
    exact bit operations and +, ×, ÷ are used: r = f·2^e with f in
    [1/√2, √2) (``frexp``), and log r = e·ln 2 + 2·atanh(s),
    s = (f - 1)/(f + 1), |s| < 0.172, by its odd series — each term
    gains a factor 34, so six terms reach f32 resolution and twelve f64;
    ln 2 is split in two so that e·ln 2 stays exact to working accuracy.
    """
    f, e = jnp.frexp(r)                                  # f in [1/2, 1)
    low = f < 0.7071067811865476
    f = jnp.where(low, 2.0 * f, f)
    e = (e - low.astype(e.dtype)).astype(r.dtype)
    s = (f - 1.0) / (f + 1.0)
    s2 = s * s
    n = 6 if jnp.dtype(r.dtype).itemsize <= 4 else 12
    poly = jnp.zeros_like(s)
    for k in range(n - 1, -1, -1):
        poly = 1.0 / (2 * k + 1) + s2 * poly
    ln2_hi, ln2_lo = 0.693145751953125, 1.4286068203094172e-06
    return e * ln2_hi + (e * ln2_lo + 2.0 * s * poly)


def _exp(h: Array) -> Array:
    """exp h to working accuracy on every backend (the TPU's f32 ``exp``
    is off by up to ~100 ulps): h = k·ln 2 + r with |r| ≤ ln2/2, exp r by
    its Taylor series (eight terms for f32, fourteen for f64) and the
    power of two applied exactly by ``ldexp``."""
    k = jnp.round(h / 0.6931471805599453)
    r = (h - k * 0.693145751953125) - k * 1.4286068203094172e-06
    n = 8 if jnp.dtype(h.dtype).itemsize <= 4 else 14
    p = jnp.ones_like(r)
    for i in range(n - 1, 0, -1):
        p = 1.0 + r * p / i
    return jnp.ldexp(p, k.astype(jnp.int32))


def _secular_bisect(d: Array, z2: Array, sigma: Array, iters: int,
                    defl: Array | None = None) -> tuple[Array, Array]:
    """All roots of 1 + sigma * sum_i z2_i/(d_i - t), sigma > 0, d ascending,
    each as ``org + tau``: ``org`` the pole nearer to the root, ``tau``
    the root's offset from it.

    Root j lives in (d_j, next pole) for j < M-1 and (d_{M-1}, d_{M-1} +
    sigma*sum(z2)) for the top root (paper eq. 5).  A root can sit much
    closer to a pole than the pole's own rounding unit (a small z_j moves
    its eigenvalue by ~sigma·z_j²), so it is never formed as an absolute
    value: the distances d_i - root = (d_i - org) - tau keep full relative
    accuracy, which the Gu–Eisenstat z-recompute and the Cauchy
    eigenvector factor both divide by (the LAPACK dlaed4 device of
    solving in coordinates shifted to the nearer pole).  One evaluation
    at the bracket's midpoint picks the nearer pole; ``iters`` geometric
    bisection steps then shrink |tau| over [half·eps², half] (half the
    bracket), so the offset converges in RELATIVE terms, ~32·2^-iters,
    however close the root lies to its pole.  Fully vectorized over all
    M roots.

    ``defl`` marks deflated poles (z_i == 0, Bunch §4): their eigenvalue
    stays AT the pole (tau = 0), and the bracket of every other root skips
    over them (the upper end is the next NON-deflated pole) — otherwise a
    root to the right of a deflated pole is lost and the pole
    double-counted.
    """
    eps = _eps_for(d.dtype)
    znorm2 = jnp.sum(z2)
    top = d[-1] + sigma * znorm2 + eps
    if defl is None:
        hi = jnp.concatenate([d[1:], top[None]])
    else:
        d_nd = jnp.where(defl, jnp.inf, d)
        nxt = jnp.concatenate(
            [jax.lax.cummin(d_nd[::-1])[::-1][1:], jnp.asarray([jnp.inf],
                                                               d.dtype)])
        hi = jnp.where(jnp.isinf(nxt), top, nxt)
    half = 0.5 * (hi - d)

    def w_at(D: Array, tau: Array) -> Array:
        # D: (M poles, M roots) pole-to-origin distances, tau: (M,)
        den = _safe_den(D - tau[None, :])
        return 1.0 + sigma * jnp.sum(z2[:, None] / den, axis=0)

    # w increases between poles: w > 0 at the midpoint puts the root in
    # the left half, nearer d_j; otherwise nearer the right end.
    left = w_at(d[:, None] - d[None, :], half) > 0.0
    org = jnp.where(left, d, hi)
    sgn = jnp.where(left, 1.0, -1.0).astype(d.dtype)
    D = d[:, None] - org[None, :]

    def body(_, lohi):
        lo, up = lohi
        # sqrt of each end: lo·up can fall below the smallest normal
        # number, which a TPU flushes to zero.
        mid = jnp.sqrt(lo) * jnp.sqrt(up)
        # From the left pole the root moves right as |tau| grows, from
        # the right one left: either way w > 0 means a smaller |tau| when
        # the origin is the left pole and a larger one otherwise.
        smaller = (w_at(D, sgn * mid) > 0.0) == left
        return jnp.where(smaller, lo, mid), jnp.where(smaller, mid, up)

    lo, up = jax.lax.fori_loop(0, iters, body, (half * eps * eps, half))
    tau = sgn * jnp.sqrt(lo) * jnp.sqrt(up)
    if defl is not None:
        org = jnp.where(defl, d, org)
        tau = jnp.where(defl, 0.0, tau)
    return org, tau


def _cluster_merge(d: Array, z: Array, tol: Array):
    """LAPACK dlaed2-style cluster deflation, vectorized.

    Poles closer than ``tol`` cannot be separated by the secular solver and
    wreck the Cauchy eigenvector columns (the near-zero cluster that mean-
    centering + near-duplicate points create on every real dataset).  For
    each run of near-equal poles, a Householder reflector H (block-diagonal
    over runs) rotates the run's z-mass into its member of largest |z|;
    the others become exactly zero and deflate.  Replacing D by H D H ≈ D
    moves each small mass by at most the run width while the dominant
    direction keeps its own pole — the error weighting of LAPACK's
    |t·c·s| ≤ tol test (a large mass rotated onto a neighbouring pole
    would instead shift the extreme root by the whole run width).

    Returns (z_new, apply, fired) where apply(X) = H @ X in O(M²) via
    segment sums (no extra matmul: the paper's 2m³-per-update flop count is
    preserved) and ``fired`` is a traced bool — True iff H is not the
    identity, i.e. a merge actually rotates z-mass.  ``fired`` is what the
    fused pair path conds on to fall back to this sequential pipeline.
    """
    M = d.shape[0]
    gap = jnp.diff(d)
    new_seg = jnp.concatenate([jnp.ones((1,), bool), gap > tol])
    seg = jnp.cumsum(new_seg.astype(jnp.int32)) - 1          # (M,)
    ones = jnp.ones_like(z)
    seg_size = jax.ops.segment_sum(ones, seg, num_segments=M)[seg]
    z2sum = jax.ops.segment_sum(z * z, seg, num_segments=M)[seg]
    znorm_seg = jnp.sqrt(z2sum)
    az = jnp.abs(z)
    idx = jnp.arange(M)
    big = az == jax.ops.segment_max(az, seg, num_segments=M)[seg]
    keep = idx == jax.ops.segment_min(jnp.where(big, idx, M), seg,
                                      num_segments=M)[seg]
    z_keep = jax.ops.segment_sum(jnp.where(keep, z, 0.0), seg,
                                 num_segments=M)[seg]
    sl = jnp.where(z_keep >= 0, 1.0, -1.0)
    target = -sl * znorm_seg                  # H z_run = target · e_keep
    w = z - jnp.where(keep, target, 0.0)
    wnorm2 = jax.ops.segment_sum(w * w, seg, num_segments=M)[seg]
    tiny = jnp.finfo(d.dtype).tiny
    active = (seg_size > 1.5) & (wnorm2 > tiny)
    coef = jnp.where(active, 2.0 / jnp.where(active, wnorm2, 1.0), 0.0)

    def apply(X: Array) -> Array:             # H @ X, rows mixed per run
        s = jax.ops.segment_sum(w[:, None] * X, seg, num_segments=M)[seg]
        return X - (coef * w)[:, None] * s

    wz = jax.ops.segment_sum(w * z, seg, num_segments=M)[seg]
    z_new = z - coef * w * wz
    # exact zeros on merged (non-kept) members so deflation catches them
    z_new = jnp.where(active & ~keep, 0.0, z_new)
    return z_new, apply, jnp.any(active)


def _gu_zhat(d: Array, org: Array, tau: Array, sigma: Array,
             z: Array) -> Array:
    """Gu–Eisenstat recomputation of |z| from the computed roots.

    sigma * ẑ_i^2 = (root_i - d_i) * prod_{j != i} (root_j - d_i)/(d_j - d_i).

    Roots arrive pole-relative (root_j = org_j + tau_j, see
    ``_secular_bisect``), so every root_j - d_i keeps its relative
    accuracy, and the product is a sum of the logs of the RATIOS: far
    from i a ratio is close to 1 and its log (``_log``) carries an
    absolute error of order eps·|log| — summing the logs of numerators
    and denominators apart would carry eps·|log d| per term and cost the
    eigenvectors their orthogonality at large M.  Under interlacing every
    ratio is positive for a non-deflated i.  Deflated/inactive entries
    (root_j == d_j exactly) contribute a ratio of exactly 1 to every
    other product, so they are skipped rather than divided out: a TPU's
    f32 division returns x/x one ulp off 1 for some x, and the hundreds
    of deflated poles of a clustered spectrum then biased every ẑ_i by
    ~1e-5, differently for each i (2e-5 of orthogonality per update at
    M=2048 on a v5e).  Their own ẑ_i is 0 exactly; coincident poles (a
    run the cluster merge left with one live member) contribute 1 as well.
    """
    tiny = jnp.finfo(d.dtype).tiny
    num = (org[None, :] - d[:, None]) + tau[None, :]       # root_j - d_i
    den = d[None, :] - d[:, None]                          # d_j - d_i
    eye = jnp.eye(d.shape[0], dtype=bool)
    pinned = (org == d) & (tau == 0.0)                     # root_j == d_j
    skip = ~eye & ((den == 0.0) | pinned[None, :])
    ratio = jnp.where(eye, num, num / jnp.where(eye | skip, 1.0, den))
    terms = jnp.where(skip, 0.0, _log(jnp.abs(ratio) + tiny))
    log_z2 = jnp.sum(terms, axis=1) - _log(jnp.abs(sigma))
    zhat = jnp.sign(z) * _exp(0.5 * log_z2)
    # Guard: if the identity degenerates numerically, fall back to z.
    ok = jnp.isfinite(zhat)
    return jnp.where(ok, zhat, z)


def _cauchy_W(d: Array, org: Array, tau: Array, zhat: Array
              ) -> tuple[Array, Array]:
    """W[i, j] = zhat_i / (d_i - root_j) and per-column inverse norms,
    with root_j = org_j + tau_j (pole-relative, see ``_secular_bisect``)."""
    den = _safe_den((d[:, None] - org[None, :]) - tau[None, :])
    W = zhat[:, None] / den
    norms = jnp.sqrt(jnp.sum(W * W, axis=0))
    inv = jnp.where(norms > 0, 1.0 / norms, 1.0)
    return W, inv


def _update_body(L: Array, U: Array, v: Array, sigma: Array, m: Array, *,
                 iters: int, method: str, matmul: str, precise: bool,
                 z: Array | None = None, row_offset: Array | None = None
                 ) -> tuple[Array, Array]:
    """Un-jitted body of ``rank_one_update`` (reused by the fused pair's
    cond-guarded merge fallback, which must inline it under one jit).

    ``U`` may be a (R, M) row block of the full eigenvector matrix, in
    which case ``z`` = Uᵀv must be supplied precomputed (the distributed
    path obtains it with one psum over the row shards) and ``row_offset``
    names the block's first global row so the Pallas rotation can prune
    along the row axis too.  With z=None (default) it is computed locally
    from the full square U — the original single-device semantics.
    """
    M = L.shape[0]
    dtype = L.dtype
    mask = active_mask(M, m)

    with jax.named_scope("kpca.krow"):
        if z is None:
            v = jnp.where(mask, v, 0.0)
            z = jnp.matmul(U.T, v, precision=MATMUL_PRECISION)
        else:
            z = jnp.where(mask, z, 0.0)
    # Deflation (Bunch §4, the case the paper handles by exclusion in §5):
    # eigendirections with |z_i| ~ 0 do not move — zero them out, pin their
    # roots at the poles, and skip them in every other root's bracket.
    # (Centering makes K' exactly singular along 1, and near-duplicate
    # points cluster eigenvalues near 0, so this path is exercised on every
    # real dataset, not just in corner cases.)
    with jax.named_scope("kpca.solve"):
        sig_abs = jnp.abs(sigma)

        # Re-sentinelize with head-room for the top root's travel, then
        # apply the flip identity so the effective sigma is positive.
        # Under the flip the sentinels land (negated) at the *bottom* of
        # the array, still sorted.
        room = sig_abs * jnp.sum(z * z)
        d_sent = sentinelize(L, m, room)

        # Cluster-merge deflation (dlaed2-style): rotate the z-mass of runs
        # of near-equal poles into one member; U absorbs the block
        # reflector at O(M²). Sentinels are spaced by 1 ≫ tol and never
        # merge.
        scale = jnp.max(jnp.abs(jnp.where(mask, L, 0.0))) + room + 1e-30
        tol = 64.0 * _eps_for(dtype) * scale
        z, applyH, _ = _cluster_merge(d_sent, z, tol)
        U = applyH(U.T).T                        # U @ H, no matmul

        f = _solve_factor(d_sent, z, sigma, m, scale, iters=iters,
                          method=method, precise=precise)
    with jax.named_scope("kpca.rotate"):
        U_new = _apply_factor(U, f, mask, m, matmul=matmul,
                              row_offset=row_offset)
        # Deflation can locally reorder roots (a root may legitimately
        # cross a deflated pole); the next update's interlacing needs
        # ascending order.
        perm = jnp.argsort(f.L_new)
        return f.L_new[perm], U_new[:, perm]


@partial(jax.jit, static_argnames=("iters", "method", "matmul", "precise"))
def rank_one_update(
    L: Array,
    U: Array,
    v: Array,
    sigma: Array,
    m: Array,
    *,
    iters: int = 62,
    method: Literal["gu", "bns"] = "gu",
    matmul: Literal["jnp", "pallas"] = "jnp",
    precise: bool = True,
    z: Array | None = None,
) -> tuple[Array, Array]:
    """One symmetric rank-one update of the eigendecomposition.

    L: (M,) eigenvalues ascending (sentinels above active spectrum),
    U: (M, M) eigenvectors in columns (identity on inactive columns),
    v: (M,) update vector, zero beyond the active region,
    sigma: scalar, either sign (sign handled by the flip identity),
    m: active count (traced scalar).

    ``z`` (optional) is a precomputed Uᵀv in the CURRENT basis — the fused
    ingest kernel produces it alongside the kernel row, skipping this
    update's own pass over U.

    Returns the updated (L, U), sorted ascending, same padding invariants.
    """
    return _update_body(L, U, v, sigma, m, iters=iters, method=method,
                        matmul=matmul, precise=precise, z=z)


class _Factor(NamedTuple):
    """One solved rank-one update as an original-domain Cauchy factor.

    The normalized eigenvector rotation is
    W[k, j] = z_k·inv_j/((d_k - lam_j) - tau_j) with deflated columns
    replaced by identity columns: each new eigenvalue is lam_j + tau_j,
    ``lam`` the old pole nearer to it and ``tau`` its offset from that
    pole (kept apart so the distances stay accurate, see
    ``_secular_bisect``).  ``L_new`` is the updated (pre-sort) spectrum.
    All vectors live in the original domain (the sigma<0 flip's sign is
    folded into z), so the active region is a prefix regardless of
    sigma's sign.
    """

    z: Array
    d: Array
    lam: Array
    tau: Array
    inv: Array
    defl: Array
    L_new: Array


def _solve_factor(d_sent: Array, z: Array, sigma: Array, m: Array,
                  scale: Array, *, iters: int, method: str,
                  precise: bool) -> _Factor:
    """Displacement deflation + secular solve + un-flip, as a ``_Factor``.

    The single shared solve pipeline behind ``rank_one_update`` and
    ``rank_one_update_pair`` — the deflation thresholds, the sigma<0 flip
    identity, and the precise/x64 solve-dtype policy live only here.

    Deflation (the LAPACK dlaed2 criterion): a component whose coupling
    to the update is below the representable resolution of the spectrum,
    σ·‖z‖·|z_i| ≲ eps·‖A‖, leaves its eigenpair in place to working
    accuracy — deflate it (root pinned at the pole, column = e_i,
    brackets skip it).  Components above that keep their root, however
    close to the pole: the pole-relative solve resolves it.

    The secular solve is O(M²) VPU work but numerically delicate (pole
    differences d_i - t_j); when ``precise`` and x64 is enabled it runs in
    f64 (the factor's vectors come back in the solve dtype) — negligible
    cost next to the O(M³) rotation, large drift win for f32 states.

    Un-flip: folding the flip identity's sign into z gives, exactly,
    W_eff[::-1, ::-1] == (-zhat_eff[::-1]) / (d_sent - (-roots_eff[::-1])),
    so the returned factor lives in the original domain and its active
    region is a prefix for either sigma sign — which is what lets the
    Pallas kernels prune every tile beyond ceil(m/B).
    """
    M = d_sent.shape[0]
    dtype = d_sent.dtype
    mask = active_mask(M, m)
    sig_abs = jnp.abs(sigma)
    neg = sigma < 0
    znorm = jnp.sqrt(jnp.sum(z * z))
    floor = 32.0 * _eps_for(dtype) * jnp.maximum(znorm, _eps_for(dtype))
    defl = (~mask | (jnp.abs(z) < floor)
            | (sig_abs * znorm * jnp.abs(z) < 8.0 * _eps_for(dtype) * scale))
    z = jnp.where(defl, 0.0, z)

    d_eff = jnp.where(neg, -d_sent[::-1], d_sent)
    z_eff = jnp.where(neg, z[::-1], z)
    defl_eff = jnp.where(neg, defl[::-1], defl)
    solve_dtype = (jnp.float64 if (precise and jax.config.jax_enable_x64)
                   else dtype)
    d_s = d_eff.astype(solve_dtype)
    z_s = z_eff.astype(solve_dtype)
    sig_s = sig_abs.astype(solve_dtype)
    org_eff, tau_eff = _secular_bisect(d_s, z_s * z_s, sig_s, iters,
                                       defl=defl_eff)
    if method == "gu":
        zhat_eff = _gu_zhat(d_s, org_eff, tau_eff, sig_s, z_s)
        zhat_eff = jnp.where(defl_eff, 0.0, zhat_eff)
    else:
        zhat_eff = z_s
    _, inv_eff = _cauchy_W(d_s, org_eff, tau_eff, zhat_eff)
    inv_eff = jnp.where(defl_eff, 1.0, inv_eff)

    z_o = jnp.where(neg, -zhat_eff[::-1], zhat_eff)
    lam_o = jnp.where(neg, -org_eff[::-1], org_eff)
    tau_o = jnp.where(neg, -tau_eff[::-1], tau_eff)
    inv_o = jnp.where(neg, inv_eff[::-1], inv_eff)
    L_new = jnp.where(mask, (lam_o + tau_o).astype(dtype), d_sent)
    return _Factor(z=jnp.where(mask, z_o, 0.0),
                   d=d_sent.astype(solve_dtype), lam=lam_o, tau=tau_o,
                   inv=inv_o, defl=defl, L_new=L_new)


def _apply_factor(U: Array, f: _Factor, mask: Array, m: Array, *,
                  matmul: str, row_offset: Array | None = None) -> Array:
    """U @ Ŵn for a single factor, preserving the padding invariants.

    ``U`` may be a row *block* of the full eigenvector matrix (the
    distributed row-sharded path rotates only its local rows): every
    overwrite below selects old columns of ``U`` itself, never a fresh
    identity, so the result is exact for any row count.  The Pallas kernel
    accepts rectangular (R, M) blocks; ``row_offset`` (the block's first
    global row) lets it prune row tiles beyond the active prefix as well,
    which is what keeps per-update MXU work at O(m_rows·m²) on P > 1
    meshes.  Pruned rows of active columns come back as zeros — their
    true value, since z is masked beyond the active prefix.
    """
    dtype = U.dtype
    if matmul == "pallas":
        # The factor is regenerated tile-by-tile in VMEM from O(M) vectors
        # (see kernels/eigvec_update), with tiles beyond the active range
        # pruned along rows, columns and the reduction axis.
        from repro.kernels.eigvec_update import ops as _ops
        z_k = jnp.where(mask, f.z.astype(dtype), 0.0)
        d_k = jnp.where(mask, f.d.astype(dtype), 2e30)
        lam_k = jnp.where(mask, f.lam.astype(dtype), 1e30)
        tau_k = jnp.where(mask, f.tau.astype(dtype), 0.0)
        inv_k = jnp.where(mask, f.inv.astype(dtype), 0.0)
        C = _ops.rotate_vectors(U, z_k, d_k, lam_k, inv_k, m, row_offset,
                                tau=tau_k)
        # f.defl ⊇ ~mask (inactive entries always deflate), so this also
        # restores the pruned inactive columns — which are the block's own
        # rows of identity columns by invariant.
        return jnp.where(f.defl[None, :], U, C)
    from repro.kernels.eigvec_update.ref import cauchy_factor_ref
    Wn = cauchy_factor_ref(f.z, f.d, f.lam, f.inv, f.defl.astype(f.z.dtype),
                           tau=f.tau).astype(dtype)
    return jnp.matmul(U, Wn, precision=MATMUL_PRECISION)


def _pair_factor(L: Array, z: Array, sigma: Array, m: Array, *, iters: int,
                 method: str, precise: bool) -> _Factor:
    """Sentinelize + solve one update into a Cauchy factor (no U rotation).

    ``rank_one_update``'s pipeline minus the dlaed2 cluster-merge, whose
    block reflector is not a Cauchy factor and so cannot sit between the
    two fused rotations.  Displacement deflation (in ``_solve_factor``)
    still guards every degenerate direction (the paper itself handles
    z_i = 0 by exclusion and has no cluster-merge either); extremely
    clustered spectra lose some of the beyond-paper orthogonality
    polish — use the sequential path when that matters more than HBM
    traffic.
    """
    mask = active_mask(L.shape[0], m)
    room = jnp.abs(sigma) * jnp.sum(z * z)
    d_sent = sentinelize(L, m, room)
    scale = jnp.max(jnp.abs(jnp.where(mask, L, 0.0))) + room + 1e-30
    return _solve_factor(d_sent, z, sigma, m, scale, iters=iters,
                         method=method, precise=precise)


def _factor_tmatvec(f: _Factor, y: Array) -> Array:
    """(Ŵn)ᵀ y in O(M²) from the factor's vectors — never materializes U's
    rotation, which is what lets the second secular solve run before the
    first eigenvector rotation has happened."""
    den = _safe_den((f.d[:, None] - f.lam[None, :]) - f.tau[None, :])
    s = jnp.sum((f.z * y)[:, None] / den, axis=0) * f.inv
    return jnp.where(f.defl, y, s)


class _PairFactors(NamedTuple):
    """Both solved factors of a fused ±sigma pair.

    Factor 1's columns carry the inter-update sort (lam1/tau1/inv1/defl1 are
    already permuted; cid1 records the permutation so deflated columns
    become e_{cid1[j]}).  ``L_new`` is the post-update-2 spectrum before
    the final ``perm2`` sort; ``merge_fired`` flags that a dlaed2
    cluster-merge would fire on either update, in which case the fused
    rotation is unsafe and callers should fall back to the sequential
    two-update path.
    """

    z1: Array
    d1: Array
    lam1: Array
    tau1: Array
    inv1: Array
    defl1: Array
    cid1: Array
    z2: Array
    d2: Array
    lam2: Array
    tau2: Array
    inv2: Array
    defl2: Array
    cid2: Array
    L_new: Array
    perm2: Array
    merge_fired: Array


def _merge_fires(L: Array, z: Array, sigma: Array, m: Array) -> Array:
    """Would ``rank_one_update``'s dlaed2 cluster-merge rotate z-mass for
    this (spectrum, z, sigma)?  Same sentinelization + tolerance as the
    sequential path, detection only (the reflector is discarded)."""
    M = L.shape[0]
    mask = active_mask(M, m)
    room = jnp.abs(sigma) * jnp.sum(z * z)
    d_sent = sentinelize(L, m, room)
    scale = jnp.max(jnp.abs(jnp.where(mask, L, 0.0))) + room + 1e-30
    tol = 64.0 * _eps_for(L.dtype) * scale
    _, _, fired = _cluster_merge(d_sent, z, tol)
    return fired


@jax.named_scope("kpca.solve")
def _pair_solve(L: Array, z1: Array, sigma1: Array, z2_raw: Array,
                sigma2: Array, m: Array, *, iters: int, method: str,
                precise: bool) -> _PairFactors:
    """Solve both secular systems of a fused pair — no U rotation.

    ``z2_raw`` is Uᵀv₂ in the *pre-update* basis; the second update's
    z₂ = U₁ᵀv₂ is recovered via the Cauchy transpose-matvec (O(M²)), so
    neither solve ever touches U.  Shared by the local fused path and the
    row-sharded distributed path (where Uᵀv needs one psum and everything
    here runs replicated).
    """
    M = L.shape[0]
    dtype = L.dtype
    f1 = _pair_factor(L, z1, sigma1, m, iters=iters, method=method,
                      precise=precise)
    perm1 = jnp.argsort(f1.L_new)
    L1 = f1.L_new[perm1]

    y = _factor_tmatvec(f1, z2_raw.astype(f1.z.dtype))
    z2 = y[perm1].astype(dtype)
    f2 = _pair_factor(L1, z2, sigma2, m, iters=iters, method=method,
                      precise=precise)
    perm2 = jnp.argsort(f2.L_new)

    fired = _merge_fires(L, z1, sigma1, m) | _merge_fires(L1, z2, sigma2, m)
    # Sentinels sort to themselves, so inactive cid stays the column index.
    cid1 = perm1.astype(jnp.int32)
    cid2 = jnp.arange(M, dtype=jnp.int32)
    return _PairFactors(z1=f1.z, d1=f1.d, lam1=f1.lam[perm1],
                        tau1=f1.tau[perm1], inv1=f1.inv[perm1],
                        defl1=f1.defl[perm1], cid1=cid1,
                        z2=f2.z, d2=f2.d, lam2=f2.lam, tau2=f2.tau,
                        inv2=f2.inv,
                        defl2=f2.defl, cid2=cid2, L_new=f2.L_new,
                        perm2=perm2, merge_fired=fired)


@jax.named_scope("kpca.rotate")
def _pair_rotate_block(U: Array, pf: _PairFactors, m: Array, *,
                       matmul: str, row_offset: Array | None = None
                       ) -> Array:
    """Fused double rotation (U @ W1n @ W2n)[:, perm2] of a row block.

    Like ``_apply_factor``, ``U`` may be a rectangular row block of the
    full eigenvector matrix: the dense route's deflated/inactive columns
    are e_{cid} columns of the factors themselves, so no full-height
    identity is ever needed, and the Pallas kernel takes (R, M) operands
    with ``row_offset`` naming the block's first global row (row-axis
    pruning).  Columns pruned by the kernel (>= the active tile range)
    are restored from ``U`` itself — by invariant those columns of any
    row block are the block's rows of identity columns.
    """
    M = U.shape[-1]
    dtype = U.dtype
    if matmul == "pallas":
        from repro.kernels.eigvec_update import ops as _ops
        C = _ops.rotate_vectors2(
            U,
            pf.z1.astype(dtype), pf.d1.astype(dtype), pf.lam1.astype(dtype),
            pf.inv1.astype(dtype), pf.defl1.astype(dtype), pf.cid1,
            pf.z2.astype(dtype), pf.d2.astype(dtype), pf.lam2.astype(dtype),
            pf.inv2.astype(dtype), pf.defl2.astype(dtype), pf.cid2,
            m, row_offset, tau1=pf.tau1.astype(dtype),
            tau2=pf.tau2.astype(dtype))
        mask = active_mask(M, m)
        C = jnp.where(mask[None, :], C, U)
    else:
        from repro.kernels.eigvec_update.ref import cauchy_factor_ref
        W1 = cauchy_factor_ref(pf.z1, pf.d1, pf.lam1, pf.inv1,
                               pf.defl1.astype(pf.z1.dtype), pf.cid1,
                               tau=pf.tau1).astype(dtype)
        W2 = cauchy_factor_ref(pf.z2, pf.d2, pf.lam2, pf.inv2,
                               pf.defl2.astype(pf.z2.dtype), pf.cid2,
                               tau=pf.tau2).astype(dtype)
        C = jnp.matmul(jnp.matmul(U, W1, precision=MATMUL_PRECISION), W2,
                       precision=MATMUL_PRECISION)
    return C[:, pf.perm2]


@partial(jax.jit, static_argnames=("iters", "method", "matmul", "precise",
                                   "merge_fallback"))
def rank_one_update_pair(
    L: Array,
    U: Array,
    v1: Array,
    sigma1: Array,
    v2: Array,
    sigma2: Array,
    m: Array,
    *,
    iters: int = 62,
    method: Literal["gu", "bns"] = "gu",
    matmul: Literal["jnp", "pallas"] = "jnp",
    precise: bool = True,
    merge_fallback: bool = True,
    z1: Array | None = None,
    z2: Array | None = None,
) -> tuple[Array, Array]:
    """Two back-to-back rank-one updates with ONE fused double rotation.

    Semantically ``rank_one_update(·, v2, sigma2) ∘ rank_one_update(·, v1,
    sigma1)`` — the ±sigma pairs of Algorithms 1 and 2 — except the U
    rotation happens once: C = U @ W1n @ W2n.  The second update's
    z₂ = U₁ᵀ v₂ is obtained without U₁ via the Cauchy transpose-matvec
    (O(M²)), so U is read and written exactly once per streamed point —
    half the HBM round-trips of two sequential updates.

    The dlaed2 cluster-merge cannot sit between the two fused rotations
    (its block reflector is not a Cauchy factor); with ``merge_fallback``
    (default) a lax.cond re-runs the pair through the sequential two-update
    path whenever a merge would fire on either update, so clustered spectra
    keep the full orthogonality polish.  The solves (O(M²·iters)) always
    run; only the O(M³) rotation is conditional — merges are rare, so the
    fused rotation is what executes in the steady state.

    matmul='jnp' materializes both factors densely (reference semantics,
    still one pass over U); 'pallas' generates both factors' tiles in VMEM
    (``eigvec_rotate2``) with active-tile pruning.

    ``z1``/``z2`` (optional, both or neither) are precomputed Uᵀv₁ / Uᵀv₂
    in the CURRENT basis — the fused ingest kernel emits them with the
    kernel row, eliminating this function's own projection pass over U.
    The merge fallback reuses z1 for its first sequential update (same
    basis) and recomputes z2 from the rotated U1 itself.
    """
    M = L.shape[0]
    mask = active_mask(M, m)
    v1 = jnp.where(mask, v1, 0.0)
    v2 = jnp.where(mask, v2, 0.0)

    with jax.named_scope("kpca.krow"):
        if z1 is None:
            # one pass over U for both z
            Z = jnp.matmul(U.T, jnp.stack([v1, v2], axis=1),
                           precision=MATMUL_PRECISION)
            z1, z2 = Z[:, 0], Z[:, 1]
        else:
            z1 = jnp.where(mask, z1, 0.0)
            z2 = jnp.where(mask, z2, 0.0)
    pf = _pair_solve(L, z1, sigma1, z2, sigma2, m, iters=iters,
                     method=method, precise=precise)

    def _fused(U):
        return pf.L_new[pf.perm2], _pair_rotate_block(U, pf, m,
                                                      matmul=matmul)

    if not merge_fallback:
        return _fused(U)

    def _sequential(U):
        # z1 is valid for the first update (same basis); the second update
        # needs U1ᵀv2, which _update_body recomputes from the rotated U1.
        L1, U1 = _update_body(L, U, v1, sigma1, m, iters=iters,
                              method=method, matmul=matmul, precise=precise,
                              z=z1)
        return _update_body(L1, U1, v2, sigma2, m, iters=iters,
                            method=method, matmul=matmul, precise=precise)

    return jax.lax.cond(pf.merge_fired, _sequential, _fused, U)


def expand_eigensystem_perm(L: Array, lam_new: Array, m: Array
                            ) -> tuple[Array, Array, Array]:
    """Eigenvalue half of ``expand_eigensystem``: the sorted spectrum plus
    the column permutation to apply to U (and to any precomputed Uᵀv — the
    fused ingest path permutes its projections instead of U twice)."""
    m_new = m + 1
    L = L.at[m].set(lam_new)
    L = sentinelize(L, m_new, jnp.zeros((), L.dtype))
    perm = jnp.argsort(L)
    return L[perm], perm, m_new


@partial(jax.jit, static_argnames=())
def expand_eigensystem(L: Array, U: Array, lam_new: Array, m: Array
                       ) -> tuple[Array, Array, Array]:
    """Append eigenpair (lam_new, e_m) and restore ascending order.

    Because inactive columns are identity, appending is just writing L[m];
    a single argsort-permutation of (L, U-columns) then restores order.
    (Paper Alg. 1 line 2 writes k/4 into the U corner — an erratum; the new
    unit eigenvector must be e_{m+1}.)
    """
    L_new, perm, m_new = expand_eigensystem_perm(L, lam_new, m)
    with jax.named_scope("kpca.rotate"):
        return L_new, U[:, perm], m_new


def reconstruct(L: Array, U: Array, m: Array) -> Array:
    """K̃ = U diag(L) U^T restricted to the active block (testing utility)."""
    M = L.shape[0]
    mask = active_mask(M, m)
    Lm = jnp.where(mask, L, 0.0)
    K = jnp.matmul(U * Lm[None, :], U.T, precision=MATMUL_PRECISION)
    blk = mask[:, None] & mask[None, :]
    return jnp.where(blk, K, 0.0)
