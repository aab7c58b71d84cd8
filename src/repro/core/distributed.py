"""Distributed incremental KPCA / Nyström via shard_map (data-parallel rows).

Sharding scheme (designed for the production mesh in ``repro.launch.mesh``):

* U (M×M eigenvectors) and the stored points X are **row-sharded** over the
  'data' axis: each device owns M/P rows (data points).  Eigenvalues L and
  all O(M) bookkeeping are replicated.
* One update needs a single collective: z = psum_p(U_p^T v_p)  (M floats).
  The secular solve (O(M^2) VPU) is replicated — cheaper than communicating.
  The Cauchy factor is built replicated from O(M) vectors; each device
  rotates only its row block: U_p <- U_p @ W  (local matmul, no comm).
* The Nyström extension row-shards K_{n,m} over 'data' as well; the
  reconstruction B diag(1/λ) B^T is local per row-block.

All updates are constructed from an ``engine.UpdatePlan`` — the same
object that drives the local and serving paths — so the sharded body
shares ``rankone``'s factor pipeline verbatim (including the dlaed2
cluster-merge: its Householder reflector acts on U's *columns*, which are
local to every row block).  ``plan.matmul`` selects the rotation backend:
the Pallas kernels take rectangular (M/P, M) row blocks directly, with
each block's ``row_offset`` (= axis_index · M/P) driving row-axis
active-tile pruning, so P > 1 meshes keep the paper's O(m³) per-update
flop count instead of falling back to dense O(M³/P) rotations.  The
fused spellings ('jnp2'/'pallas2') route ±sigma pairs through
``make_sharded_update_pair``.

``plan.dispatch == "bucketed"`` additionally slices every *local* operand
to the active power-of-two bucket before the update — row blocks become
(min(M/P, M_b), M_b) rectangles — so the replicated secular solve runs at
O(M_b²·iters) and the rotation at the bucket size, mirroring the engine's
single-stream bucketed dispatch.  The global (sharded) shapes never
change, so the slicing composes with any mesh; each bucket rung compiles
once (host-side ``int(m)`` read per call, as in ``engine.rank_one``).

Fused-pair merge fallback (``plan.merge_fallback``): the fused rotation
skips the dlaed2 cluster-merge, so clustered spectra need the sequential
two-update path.  Collectives inside a ``lax.cond`` branch would deadlock
a multi-device mesh if any device disagreed on the predicate, so the pair
body is *collective-balanced*: BOTH psums are always issued outside the
conds (the fused steady state pays one redundant O(M) all-reduce), and
the cond branches contain only local compute.  The merge predicate is a
deterministic function of replicated operands, so every device takes the
same branch.

Per update the communication volume is M floats (one all-reduce; two for
a guarded fused pair) against O(M_b²·m/P) local flops — strongly
compute-bound for M ≳ P, which is what the roofline analysis in
EXPERIMENTS.md shows.

Decremental path: ``make_sharded_downdate`` evicts the boundary row
(victim pre-permuted by the host); ``make_sharded_evict`` lifts that
restriction with an IN-GRAPH boundary permutation (one ppermute moving
each device's boundary row + one psum gathering the victim row along
the replicated axis), so the victim index may be traced.
``make_sharded_window_block`` composes evict + ingest into the scanned
steady-state sliding-window engine (m ≡ W, unadjusted system; X and
the arrival ring replicated) — every collective in the step is
unconditional, preserving the deadlock-free discipline above.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import downdate as dd
from repro.core import engine as eng
from repro.core import kernels_fn as kf, rankone
from repro.core.precision import MATMUL_PRECISION

Array = jax.Array


def _solve_kwargs(plan: eng.UpdatePlan, dtype) -> dict:
    return dict(iters=eng.resolve_iters(plan.iters, dtype),
                method=plan.method, precise=plan.precise)


def _rank_one_update_sharded(L, U_local, v_local, sigma, m, *,
                             axis: str, plan: eng.UpdatePlan,
                             rows_full: int | None = None):
    """Body run under shard_map: U_local is a row block of U.

    z comes from ONE psum; everything after is ``rankone._update_body`` —
    the exact single-device pipeline (deflation thresholds, dlaed2
    cluster-merge, flip identity, secular bisection) run replicated, with
    only the row-block rotation local.  ``rankone._apply_factor`` routes
    it through the rectangular Pallas kernel with row/column active-tile
    pruning (``row_offset`` = this device's first global row; bucketed
    dispatch passes the pre-slicing local row count as ``rows_full`` so
    the offset stays the global one).
    """
    r0 = jax.lax.axis_index(axis) * (rows_full or U_local.shape[0])
    z = jax.lax.psum(
        jnp.matmul(U_local.T, v_local, precision=MATMUL_PRECISION), axis)
    return rankone._update_body(L, U_local, v_local, sigma, m,
                                matmul=plan.inner_matmul, z=z, row_offset=r0,
                                **_solve_kwargs(plan, L.dtype))


def _rank_one_update_pair_sharded(L, U_local, v1_local, sigma1, v2_local,
                                  sigma2, m, *, axis: str,
                                  plan: eng.UpdatePlan,
                                  rows_full: int | None = None,
                                  Z: Array | None = None):
    """Fused ±sigma pair under shard_map, with a collective-balanced
    merge fallback.

    ONE psum carries both z vectors; z₂ = U₁ᵀv₂ for the fused path comes
    from the Cauchy transpose-matvec (replicated, no collective).  A
    caller that already holds the replicated (M, 2) projections — the
    fused k-row ingest psums them out of its own kernel pass — supplies
    ``Z`` and the psum here is skipped (a trace-time decision, identical
    on every device, so the collective schedule stays deterministic).
    When ``plan.merge_fallback`` is set, a dlaed2 cluster-merge firing on
    either update re-routes the pair through the sequential two-update
    pipeline — and to keep a multi-device mesh deadlock-free the second
    psum is ALWAYS issued (on the post-update-1 row block, which is the
    unchanged U when no merge fired), so both cond branches contain only
    local compute and every device runs an identical collective schedule.
    """
    r0 = jax.lax.axis_index(axis) * (rows_full or U_local.shape[0])
    kw = _solve_kwargs(plan, L.dtype)
    if Z is None:
        Z = jax.lax.psum(
            jnp.matmul(U_local.T, jnp.stack([v1_local, v2_local], axis=1),
                       precision=MATMUL_PRECISION), axis)
    pf = rankone._pair_solve(L, Z[:, 0], sigma1, Z[:, 1], sigma2, m, **kw)

    def _fused(U):
        return pf.L_new[pf.perm2], rankone._pair_rotate_block(
            U, pf, m, matmul=plan.inner_matmul, row_offset=r0)

    if not plan.merge_fallback:
        return _fused(U_local)

    def _seq1(U):
        return rankone._update_body(L, U, v1_local, sigma1, m, z=Z[:, 0],
                                    row_offset=r0,
                                    matmul=plan.inner_matmul, **kw)

    def _keep(U):
        return L, U

    # Stage 1 (local compute only): run sequential update 1 iff a merge
    # fires; otherwise pass the row block through untouched.
    L1, U1 = jax.lax.cond(pf.merge_fired, _seq1, _keep, U_local)
    # Collective balance: psum 2 is unconditional.  Merge-free steady
    # state: U1 == U_local, so this recomputes Z[:, 1] redundantly — the
    # O(M) price of a deadlock-free fallback.
    z2 = jax.lax.psum(
        jnp.matmul(U1.T, v2_local, precision=MATMUL_PRECISION), axis)

    def _seq2(U):
        return rankone._update_body(L1, U, v2_local, sigma2, m, z=z2,
                                    row_offset=r0,
                                    matmul=plan.inner_matmul, **kw)

    return jax.lax.cond(pf.merge_fired, _seq2, _fused, U1)


# ------------------------------------------------- bucketed local slicing --
# Soundness of the local bucket slice (L -> L[:Mb], row block ->
# (min(R, Mb), Mb)) mirrors ``engine.slice_state`` plus one sharded
# argument: every global row excluded from some device's slice has index
# >= Mb (devices past the first keep rows whose global index starts at
# R >= min(R, Mb); the first device keeps min(R, Mb) rows), and such rows
# are exact identity rows with their unit entry OUTSIDE the sliced
# columns — they contribute nothing to z and are provably unchanged by
# the update, so slicing loses nothing while m < M_b.


def _bucketed_dispatch(build, plan: eng.UpdatePlan):
    """Shared dispatch shell for every builder in this module.

    ``build(Mb)`` returns the jitted shard_map for one bucket (None =
    full capacity).  Fixed dispatch compiles once; bucketed dispatch
    reads ``int(m)`` — by convention the LAST positional argument of
    every builder's callable, with L first — on the host and caches one
    compilation per bucket rung, exactly as ``engine.rank_one``.
    """
    if plan.dispatch != "bucketed":
        return build(None)

    cache: dict[int, object] = {}

    def dispatch(*args):
        L, m = args[0], args[-1]
        M = L.shape[0]
        # A downdate/evict never grows m and an update's caller passes
        # the pre-update m, so the bucket holds m itself (full-capacity
        # states stay legal; m on a rung doesn't jump to the next one).
        Mb = eng.bucket_for(max(int(m), 1), M, plan.min_bucket)
        key = Mb if Mb < M else -1
        if key not in cache:
            cache[key] = build(None if Mb >= M else Mb)
        return cache[key](*args)

    return dispatch


def make_sharded_update(mesh, *, axis: str = "data",
                        plan: eng.UpdatePlan = eng.DEFAULT_PLAN):
    """Build a pjit-compatible sharded rank-one update over ``mesh``.

    Returns f(L, U, v, sigma, m) with U sharded P(axis, None); everything
    else replicated.  Composable under jit with other computation.  With
    ``plan.dispatch == "bucketed"`` the returned callable reads
    ``int(m)`` on the host and dispatches to a per-bucket compilation
    whose local operands are sliced to the bucket (see module docstring).
    """

    def fixed_body(L, U_local, v_local, sigma, m):
        return _rank_one_update_sharded(L, U_local, v_local, sigma, m,
                                        axis=axis, plan=plan)

    def sliced_body(Mb: int):
        def body(L, U_local, v_local, sigma, m):
            R = U_local.shape[0]
            Rb = min(R, Mb)
            Lb, Ub = _rank_one_update_sharded(
                L[:Mb], U_local[:Rb, :Mb], v_local[:Rb], sigma, m,
                axis=axis, plan=plan, rows_full=R)
            L_new = rankone.sentinelize(L.at[:Mb].set(Lb), m,
                                        jnp.zeros((), L.dtype))
            return L_new, U_local.at[:Rb, :Mb].set(Ub)

        return body

    def build(Mb: int | None):
        body = fixed_body if Mb is None else sliced_body(Mb)
        # jit the shard_map so repeated eager calls hit the compile cache
        # (bare shard_map re-traces per call).
        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(axis, None), P(axis), P(), P()),
            out_specs=(P(), P(axis, None)),
            check_vma=False,
        ))

    return _bucketed_dispatch(build, plan)


def make_sharded_update_pair(mesh, *, axis: str = "data",
                             plan: eng.UpdatePlan = eng.DEFAULT_PLAN):
    """Sharded fused ±sigma pair: f(L, U, v1, sigma1, v2, sigma2, m).

    Reads/writes each U row block once in the merge-free steady state and
    issues two psums total (one carrying both z vectors, one balancing
    the fallback — see module docstring).  ``plan.merge_fallback`` re-runs
    clustered-spectrum pairs through the sequential two-update pipeline
    under a cond whose branches are collective-free, closing the PR-2
    clustered-spectrum gap without risking a mesh deadlock.  Bucketed
    dispatch slices local operands exactly as ``make_sharded_update``.
    """

    def fixed_body(L, U_local, v1_local, sigma1, v2_local, sigma2, m):
        return _rank_one_update_pair_sharded(L, U_local, v1_local, sigma1,
                                             v2_local, sigma2, m,
                                             axis=axis, plan=plan)

    def sliced_pair_body(Mb: int):
        def body(L, U_local, v1_local, sigma1, v2_local, sigma2, m):
            R = U_local.shape[0]
            Rb = min(R, Mb)
            Lb, Ub = _rank_one_update_pair_sharded(
                L[:Mb], U_local[:Rb, :Mb], v1_local[:Rb], sigma1,
                v2_local[:Rb], sigma2, m, axis=axis, plan=plan, rows_full=R)
            L_new = rankone.sentinelize(L.at[:Mb].set(Lb), m,
                                        jnp.zeros((), L.dtype))
            return L_new, U_local.at[:Rb, :Mb].set(Ub)

        return body

    def build(Mb: int | None):
        body = fixed_body if Mb is None else sliced_pair_body(Mb)
        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(axis, None), P(axis), P(), P(axis), P(), P()),
            out_specs=(P(), P(axis, None)),
            check_vma=False,
        ))

    return _bucketed_dispatch(build, plan)


def _downdate_sharded(L, U_local, a, k_new, m, *, axis: str,
                      plan: eng.UpdatePlan, rows_full: int | None = None):
    """Row-sharded decremental update: evict the boundary point q = m−1.

    The inverse ±sigma pair reuses ``_rank_one_update_pair_sharded``
    verbatim (so it inherits the collective-balanced merge fallback);
    the kernel row ``a`` arrives REPLICATED — it is O(M) and the caller
    typically built it with one ``sharded_gram_row`` psum — and each
    device slices its local rows.  The contraction needs row q of the
    post-pair U, which lives on one shard: ONE extra psum of M floats
    broadcasts it, and the Householder that folds the decoupled
    eigenpair into an exact identity pair acts on U's *columns* — local
    to every row block, like the dlaed2 reflector.  Total per downdate:
    three psums of O(M) floats (two from the guarded pair), against the
    same O(M_b²·m/P) local rotation flops as an update.
    """
    M = L.shape[0]
    dtype = L.dtype
    R = U_local.shape[0]
    q = m - 1
    r0 = jax.lax.axis_index(axis) * (rows_full or R)
    local_idx = jnp.arange(R) + r0

    kn = jnp.maximum(k_new, jnp.finfo(dtype).tiny)
    a = jnp.where(jnp.arange(M) < q, a, 0.0)
    v1 = a.at[q].set(kn / 2.0)
    v2 = a.at[q].set(kn / 4.0)
    sigma = 4.0 / kn
    v1_l = jax.lax.dynamic_slice(v1, (r0,), (R,))
    v2_l = jax.lax.dynamic_slice(v2, (r0,), (R,))
    L, U_local = _rank_one_update_pair_sharded(
        L, U_local, v2_l, sigma, v1_l, -sigma, m, axis=axis, plan=plan,
        rows_full=rows_full)

    # Contraction: ONE psum broadcasts the global row q of the post-pair
    # U; the Householder + column permutation + identity forcing are
    # column-local and shared with the single-device path
    # (``downdate.contract_rows`` — the row block passes its global row
    # indices so the forced identity pair lands on the owner shard).
    eq_local = (local_idx == q).astype(dtype)
    w = jax.lax.psum(                                   # global row q of U
        jnp.matmul(U_local.T, eq_local, precision=MATMUL_PRECISION), axis)
    w = jnp.where(rankone.active_mask(M, m), w, 0.0)
    return dd.contract_rows(L, U_local, w, m, row_ids=local_idx)


def make_sharded_downdate(mesh, *, axis: str = "data",
                          plan: eng.UpdatePlan = eng.DEFAULT_PLAN):
    """Sharded decremental update: f(L, U, a, k_new, m) -> (L, U, m−1).

    Evicts the ACTIVE BOUNDARY point (row m−1) of the unadjusted system —
    the caller permutes the victim there first (``downdate.boundary_perm``
    is a pure function of (i, m); applying it to row-sharded U is a
    gather along the replicated dimension).  ``a`` is the victim's kernel
    row against the stored points, replicated; with
    ``plan.dispatch == "bucketed"`` local operands are sliced to the
    bucket holding m (a downdate never grows the system), exactly as in
    ``make_sharded_update``.
    """

    def fixed_body(L, U_local, a, k_new, m):
        return _downdate_sharded(L, U_local, a, k_new, m, axis=axis,
                                 plan=plan)

    def sliced_body(Mb: int):
        def body(L, U_local, a, k_new, m):
            R = U_local.shape[0]
            Rb = min(R, Mb)
            Lb, Ub, m_new = _downdate_sharded(
                L[:Mb], U_local[:Rb, :Mb], a[:Mb], k_new, m, axis=axis,
                plan=plan, rows_full=R)
            L_new = rankone.sentinelize(L.at[:Mb].set(Lb), m_new,
                                        jnp.zeros((), L.dtype))
            return L_new, U_local.at[:Rb, :Mb].set(Ub), m_new

        return body

    def build(Mb: int | None):
        body = fixed_body if Mb is None else sliced_body(Mb)
        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(axis, None), P(), P(), P()),
            out_specs=(P(), P(axis, None), P()),
            check_vma=False,
        ))

    return _bucketed_dispatch(build, plan)


def _permute_rows_sharded(rows_block, i, m, *, axis: str, nshards: int,
                          rows_full: int | None = None):
    """Row-sharded boundary permutation: move global row ``i`` to the
    active boundary q = m−1, survivors shifting up — entirely in-graph
    (``i`` and ``m`` may be traced scalars, so no host round-trip decides
    the victim).

    The permutation is a cyclic shift confined to rows [i, m−1], so each
    device needs only (a) its own rows, (b) ONE boundary row from the
    next device — a ``ppermute`` of O(M) floats — and (c) global row i
    for whichever device owns row m−1, gathered along the replicated
    axis with one O(M) psum.  Bucketed local slicing is transparent:
    either the slice keeps every per-device row (contiguous global ids)
    or the bucket fits inside device 0's block and every other device
    holds only inactive identity rows the shift never touches.  Both
    collectives are unconditional, keeping the module's
    collective-balanced discipline.
    """
    R = rows_block.shape[0]
    r0 = jax.lax.axis_index(axis) * (rows_full or R)
    gids = jnp.arange(R) + r0
    # (b) next device's first row closes each device's local shift window.
    nbr = jax.lax.ppermute(rows_block[0], axis,
                           perm=[((p + 1) % nshards, p)
                                 for p in range(nshards)])
    shifted = jnp.concatenate([rows_block[1:], nbr[None]], axis=0)
    # (c) global row i, replicated to every device.
    sel = (gids == i).astype(rows_block.dtype)
    row_i = jax.lax.psum(
        jnp.matmul(sel, rows_block, precision=MATMUL_PRECISION), axis)
    keep = (gids < i) | (gids >= m)
    last = gids == (m - 1)
    return jnp.where(keep[:, None], rows_block,
                     jnp.where(last[:, None], row_i[None, :], shifted))


def make_sharded_evict(mesh, *, axis: str = "data",
                       plan: eng.UpdatePlan = eng.DEFAULT_PLAN):
    """Sharded eviction of an ARBITRARY active row:
    f(L, U, a, k_new, i, m) -> (L, U, m−1).

    Closes the boundary-permutation follow-up of ``make_sharded_downdate``
    (which evicts row m−1 only and leaves the victim permutation to the
    host): the survivor-order-preserving permutation runs in-graph via
    ``_permute_rows_sharded``, so ``i`` may be a traced scalar — e.g. the
    FIFO-oldest ``argmin(ages)`` of a sliding window — and the whole
    evict needs no host round-trip.  ``a`` is the victim's kernel row
    against the stored points (replicated, self-entry at position i,
    inactive entries zero); ``k_new`` its diagonal value.  Cost on top of
    the boundary downdate: one O(M) ppermute + one O(M) psum.
    """
    nsh = mesh.shape[axis]

    def fixed_body(L, U_local, a, k_new, i, m):
        U_p = _permute_rows_sharded(U_local, i, m, axis=axis, nshards=nsh)
        order = dd.boundary_perm(i, m, L.shape[0])
        return _downdate_sharded(L, U_p, a[order], k_new, m, axis=axis,
                                 plan=plan)

    def sliced_body(Mb: int):
        def body(L, U_local, a, k_new, i, m):
            R = U_local.shape[0]
            Rb = min(R, Mb)
            U_p = _permute_rows_sharded(U_local[:Rb, :Mb], i, m, axis=axis,
                                        nshards=nsh, rows_full=R)
            order = dd.boundary_perm(i, m, Mb)
            Lb, Ub, m_new = _downdate_sharded(
                L[:Mb], U_p, a[:Mb][order], k_new, m, axis=axis, plan=plan,
                rows_full=R)
            L_new = rankone.sentinelize(L.at[:Mb].set(Lb), m_new,
                                        jnp.zeros((), L.dtype))
            return L_new, U_local.at[:Rb, :Mb].set(Ub), m_new

        return body

    def build(Mb: int | None):
        body = fixed_body if Mb is None else sliced_body(Mb)
        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(axis, None), P(), P(), P(), P()),
            out_specs=(P(), P(axis, None), P()),
            check_vma=False,
        ))

    return _bucketed_dispatch(build, plan)


# ------------------------------------------------- sharded window engine --
def _window_step_sharded(L, U_local, X, ages, clock, x_new, m, *,
                         axis: str, spec: kf.KernelSpec,
                         plan: eng.UpdatePlan, nshards: int,
                         rows_full: int | None = None):
    """One steady-state sliding-window step (m ≡ W) of the UNADJUSTED
    sharded eigensystem: evict the FIFO-oldest row, ingest ``x_new``,
    advance the arrival ring — all in-graph.

    U is row-sharded; L, the stored points X, and the O(M) arrival ring
    (``ages``/``clock``) are replicated, matching the module's "O(M)
    bookkeeping is replicated" scheme (X is consumed by replicated kernel
    rows, so sharding it would just add gathers).  The victim is
    ``argmin(ages)`` — a traced read — permuted to the boundary by
    ``_permute_rows_sharded``; the inverse pair + contraction and the
    forward expansion + ±sigma pair reuse the sharded bodies above, so
    the per-step collective schedule is fixed (ppermute + 6 O(M) psums,
    all unconditional) and the step composes under ``lax.scan``.

    With ``plan.health`` quarantine on, a non-finite (or kernel-row
    outlier) arrival is rejected with ZERO state mutation: the step body
    still executes unconditionally on a sanitized stand-in (the stored
    row 0) — ``x_new`` is replicated, so the verdict is identical on
    every shard and the collective schedule above stays fixed (the same
    deadlock-free discipline as the merge fallback) — and a final
    replicated elementwise select discards the result.  The clock then
    does not advance, so the caller recovers the quarantine count as
    ``T − (clock_after − clock_before)``.

    The step is the same ``gate → evict|ingest`` composition that
    ``engine.Engine.step`` assembles for single streams, built from the
    sharded stage helpers below (``_window_gate_sharded``,
    ``_window_evict_sharded``, ``_window_ingest_sharded``) — extraction
    only, op-for-op identical, so the traced collective schedule is
    unchanged.
    """
    policy = getattr(plan, "health", None)
    guard = policy is not None and policy.quarantine
    if guard:
        ok, x_new = _window_gate_sharded(x_new, X, m, spec=spec,
                                         policy=policy)
        L0, U0, X0, ages0, clock0 = L, U_local, X, ages, clock
    L1, U1, X1, ages1, m1 = _window_evict_sharded(
        L, U_local, X, ages, m, axis=axis, spec=spec, plan=plan,
        nshards=nshards, rows_full=rows_full)
    L3, U3, X2, ages2 = _window_ingest_sharded(
        L1, U1, X1, ages1, clock, x_new, m1, axis=axis, spec=spec,
        plan=plan, rows_full=rows_full)
    if guard:
        return (jnp.where(ok, L3, L0), jnp.where(ok, U3, U0),
                jnp.where(ok, X2, X0), jnp.where(ok, ages2, ages0),
                jnp.where(ok, clock + 1, clock0))
    return L3, U3, X2, ages2, clock + 1


def _window_gate_sharded(x_new, X, m, *, spec: kf.KernelSpec, policy):
    """The gate stage of the sharded window step: quarantine verdict plus
    the sanitized stand-in (stored row 0).  ``x_new`` and ``X`` are
    replicated, so the verdict is identical on every shard and no
    collective is issued — downstream stages stay schedule-fixed."""
    M = X.shape[0]
    ok = jnp.all(jnp.isfinite(x_new))
    if policy.outlier_tol > 0.0:
        x_tmp = jnp.where(ok, x_new, X[0].astype(x_new.dtype))
        a_g = kf.kernel_row(x_tmp, X, spec=spec)
        a_g = jnp.where(rankone.active_mask(M, m), a_g, 0.0)
        k_g = kf.gram_block(x_tmp[None], x_tmp[None], spec=spec)[0, 0]
        ok = ok & (jnp.max(jnp.abs(a_g)) >= policy.outlier_tol * k_g)
    return ok, jnp.where(ok, x_new, X[0].astype(x_new.dtype))


def _window_evict_sharded(L, U_local, X, ages, m, *, axis: str,
                          spec: kf.KernelSpec, plan: eng.UpdatePlan,
                          nshards: int, rows_full: int | None = None):
    """The evict stage: permute the FIFO victim (argmin of ages) to the
    boundary, inverse ±sigma pair + contraction — the sharded mirror of
    the downdate half of ``engine._window_pair`` (ppermute + 3 psums,
    unconditional)."""
    M = L.shape[0]
    victim = jnp.argmin(ages).astype(jnp.int32)
    order = dd.boundary_perm(victim, m, M)
    U_p = _permute_rows_sharded(U_local, victim, m, axis=axis,
                                nshards=nshards, rows_full=rows_full)
    X_p = X[order]
    q = m - 1
    a = kf.kernel_row(X_p[q], X_p, spec=spec)
    a = jnp.where(rankone.active_mask(M, m), a, 0.0)
    L1, U1, m1 = _downdate_sharded(L, U_p, a, a[q], m, axis=axis, plan=plan,
                                   rows_full=rows_full)
    idx = jnp.arange(M)
    X1 = jnp.where((idx == q)[:, None], 0.0, X_p)
    # No sentinel write for the freed boundary slot: at m ≡ W the ingest
    # stage stamps the same index m1 with the clock.
    ages1 = ages[order]
    return L1, U1, X1, ages1, m1


def _window_ingest_sharded(L1, U1, X1, ages1, clock, x_new, m1, *,
                           axis: str, spec: kf.KernelSpec,
                           plan: eng.UpdatePlan,
                           rows_full: int | None = None):
    """The ingest stage: expansion + forward ±sigma pair (Algorithm 1) —
    the sharded mirror of the ingest half of ``engine._window_pair``
    (one fused or separate z psum + the pair's collectives)."""
    M = L1.shape[0]
    dtype = L1.dtype
    idx = jnp.arange(M)
    k_new = kf.gram_block(x_new[None], x_new[None], spec=spec)[0, 0]
    kn = jnp.maximum(k_new, jnp.finfo(dtype).tiny)
    sigma = 4.0 / kn
    R = U1.shape[0]
    r0 = jax.lax.axis_index(axis) * (rows_full or R)
    if plan.fuse_krow:
        # Fused prologue, rectangular per-shard: ONE pass over this
        # device's (R, M) row block of U produces its slice of the masked
        # kernel row AND the partial projection Uᵀa; one psum replaces
        # the pair's own z collective (see _rank_one_update_pair_sharded).
        # Shards whose rows lie beyond a bucket slice contribute zero
        # (their global rows are >= m, masked inside the kernel).
        from repro.kernels.rbf_gram import ops as kops

        X_loc = jax.lax.dynamic_slice(
            X1, (r0, jnp.zeros((), r0.dtype)), (R, X1.shape[1]))
        a_loc, Pp = kops.krow_project(U1, X_loc, x_new,
                                      jnp.zeros((R, 0), dtype), m1, r0,
                                      spec=spec)
        p = jax.lax.psum(Pp[:, 0], axis)
        L2, perm, m2 = rankone.expand_eigensystem_perm(L1, kn / 4.0, m1)
        U2 = U1[:, perm]
        # Uᵀe_{m1} = e_{m1} pre-expansion (identity column), so the
        # expanded projections are p with slot m1 overwritten, permuted.
        Z = jnp.stack([p.at[m1].set(kn / 2.0)[perm],
                       p.at[m1].set(kn / 4.0)[perm]], axis=1)
        gids = jnp.arange(R) + r0
        v1_l = jnp.where(gids == m1, kn / 2.0, a_loc)
        v2_l = jnp.where(gids == m1, kn / 4.0, a_loc)
        L3, U3 = _rank_one_update_pair_sharded(
            L2, U2, v1_l, sigma, v2_l, -sigma, m2, axis=axis, plan=plan,
            rows_full=rows_full, Z=Z)
    else:
        a_new = kf.kernel_row(x_new, X1, spec=spec)
        a_new = jnp.where(rankone.active_mask(M, m1), a_new, 0.0)
        # expand_eigensystem only writes L and permutes U columns — both
        # device-local on a row block, so the local helper is reused as-is.
        L2, U2, m2 = rankone.expand_eigensystem(L1, U1, kn / 4.0, m1)
        v1 = a_new.at[m1].set(kn / 2.0)
        v2 = a_new.at[m1].set(kn / 4.0)
        v1_l = jax.lax.dynamic_slice(v1, (r0,), (R,))
        v2_l = jax.lax.dynamic_slice(v2, (r0,), (R,))
        L3, U3 = _rank_one_update_pair_sharded(L2, U2, v1_l, sigma, v2_l,
                                               -sigma, m2, axis=axis,
                                               plan=plan,
                                               rows_full=rows_full)
    X2 = jnp.where((idx == m1)[:, None], x_new[None, :].astype(X1.dtype), X1)
    ages2 = ages1.at[m1].set(clock)
    return L3, U3, X2, ages2


def _rebase_ring_traced(ages, clock, span: int):
    """Traced mirror of ``window.maybe_rebase``, hoisted per block: shift
    the arrival stamps down when ``clock + span`` could reach the
    sentinel (without x64 the ring is int32 and a forever stream would
    otherwise collide with it after ~10⁹ points).  Replicated elementwise
    arithmetic — deterministic on every device, no collective.
    """
    from repro.core import window as wnd

    sent = wnd.age_sentinel(ages.dtype)
    base = clock - ages.shape[0]
    reb = jnp.where(ages == sent, sent, ages - base)
    need = clock >= sent - 1 - span
    return (jnp.where(need, reb, ages),
            jnp.where(need, clock - base, clock))


def make_sharded_window_block(mesh, spec: kf.KernelSpec, *,
                              axis: str = "data",
                              plan: eng.UpdatePlan = eng.DEFAULT_PLAN):
    """Sharded steady-state window engine:
    f(L, U, X, ages, clock, xs, m) -> (L, U, X, ages, clock).

    Folds a (T, d) block into a FULL sliding window (m ≡ W, unadjusted
    system) with ONE dispatch: ``lax.scan`` over ``_window_step_sharded``
    — the distributed mirror of ``engine.Engine.window_block``'s steady
    state.  The FIFO-oldest victim of every step is chosen in-graph from
    the replicated arrival ring, and the sharded boundary permutation
    means no host round-trip anywhere inside the block.  ``m`` is
    invariant (each step nets zero), so one compilation serves the
    steady state forever; with ``plan.dispatch == "bucketed"`` every
    local operand is sliced to the bucket holding W, as in the other
    builders.  The int32 clock-rebase guard runs traced at block entry
    (``_rebase_ring_traced``), mirroring ``Engine.window_block``'s
    hoisted check, so forever streams never collide with the age
    sentinel.  Pass T = 1 blocks for a single fused step.
    """
    nsh = mesh.shape[axis]

    def fixed_body(L, U_local, X, ages, clock, xs, m):
        ages, clock = _rebase_ring_traced(ages, clock, xs.shape[0])

        def step(carry, x_new):
            L, U_local, X, ages, clock = carry
            return _window_step_sharded(
                L, U_local, X, ages, clock, x_new, m, axis=axis, spec=spec,
                plan=plan, nshards=nsh), None

        carry, _ = jax.lax.scan(step, (L, U_local, X, ages, clock), xs)
        return carry

    def sliced_body(Mb: int):
        def body(L, U_local, X, ages, clock, xs, m):
            R = U_local.shape[0]
            Rb = min(R, Mb)
            ages_b, clock = _rebase_ring_traced(ages[:Mb], clock,
                                                xs.shape[0])

            def step(carry, x_new):
                Lb, Ub, Xb, agb, clk = carry
                return _window_step_sharded(
                    Lb, Ub, Xb, agb, clk, x_new, m, axis=axis, spec=spec,
                    plan=plan, nshards=nsh, rows_full=R), None

            carry, _ = jax.lax.scan(
                step, (L[:Mb], U_local[:Rb, :Mb], X[:Mb], ages_b, clock),
                xs)
            Lb, Ub, Xb, agb, clock = carry
            L_new = rankone.sentinelize(L.at[:Mb].set(Lb), m,
                                        jnp.zeros((), L.dtype))
            return (L_new, U_local.at[:Rb, :Mb].set(Ub), X.at[:Mb].set(Xb),
                    ages.at[:Mb].set(agb), clock)

        return body

    def build(Mb: int | None):
        body = fixed_body if Mb is None else sliced_body(Mb)
        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(axis, None), P(), P(), P(), P(), P()),
            out_specs=(P(), P(axis, None), P(), P(), P()),
            check_vma=False,
        ))

    return _bucketed_dispatch(build, plan)


def make_sharded_window_block_metered(mesh, spec: kf.KernelSpec, *,
                                      axis: str = "data",
                                      plan: eng.UpdatePlan = eng.DEFAULT_PLAN):
    """Metered sharded window engine:
    f(L, U, X, ages, clock, xs, m, mstate) -> (L, U, X, ages, clock, mstate).

    Wraps the UNMODIFIED ``make_sharded_window_block`` executable — same
    shard_map body, same jit cache entry, bitwise-identical eigensystem —
    and accounts the block into a riding ``telemetry.MetricsState`` from
    replicated outputs only: the accepted count is the clock delta (the
    guarded step advances the clock only on acceptance), m is invariant
    at the full window so every accepted fold evicted one point.  The
    note consumes replicated scalars, so the MetricsState stays
    shard-consistent without adding a single collective — the fixed
    ppermute/psum schedule inside the block is untouched.
    """
    from repro.core import telemetry as tm

    inner = make_sharded_window_block(mesh, spec, axis=axis, plan=plan)

    def fn(L, U_local, X, ages, clock, xs, m, mstate):
        out = inner(L, U_local, X, ages, clock, xs, m)
        clock_after = out[4]
        mstate = tm.note_block(mstate, m, m, xs.shape[0],
                               clock_after - clock)
        # m ≡ W on this path by contract: the window is always full.
        mstate = mstate._replace(
            window_fill=jnp.ones((), mstate.window_fill.dtype))
        return out + (mstate,)

    return fn


def make_sharded_expand(mesh, *, axis: str = "data"):
    """Sharded version of expand_eigensystem: permutation applies to columns
    (replicated dimension), so each row block permutes locally."""

    def body(L, U_local, lam_new, m):
        m_new = m + 1
        L = L.at[m].set(lam_new)
        L = rankone.sentinelize(L, m_new, jnp.zeros((), L.dtype))
        perm = jnp.argsort(L)
        return L[perm], U_local[:, perm], m_new

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(axis, None), P(), P()),
        out_specs=(P(), P(axis, None), P()),
        check_vma=False,
    ))


def sharded_gram_row(mesh, spec: kf.KernelSpec, *, axis: str = "data"):
    """k(X, x_new) with X row-sharded: embarrassingly parallel."""

    def body(X_local, x_new):
        return kf.kernel_row(x_new, X_local, spec=spec)

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(P(axis, None), P()),
                                 out_specs=P(axis), check_vma=False))


# ------------------------------------------------ tenant x row 2-D mesh --
# Multi-tenant serving shards the TENANT axis of stacked (B, ...) states
# over a second mesh dimension: a (P_t, P_r) mesh places B/P_t tenants on
# each tenant slice, and within a slice each tenant's U is row-sharded
# over the P_r 'data' devices exactly as in the 1-D builders above.  The
# update body is the SAME collective-balanced `_rank_one_update_pair_-
# sharded`, vmapped over the local tenants: its psums name only the row
# axis, so vmap batches them into one fused all-reduce per tenant slice
# and the tenant axis needs zero collectives — tenants are independent
# eigensystems.  Queries against published snapshots are likewise
# embarrassingly parallel over tenants.


def make_tenant_mesh(p_tenant: int, p_rows: int, *, devices=None):
    """A (tenant, data) 2-D mesh of P_t x P_r devices.

    Row 0 varies the 'data' axis fastest, so the P_r-device row meshes of
    a tenant slice are contiguous device groups — the layout the 1-D
    builders assume when a tenant slice degenerates to P_t = 1.
    """
    import numpy as np

    devs = np.asarray(jax.devices() if devices is None
                      else devices).reshape(-1)
    need = p_tenant * p_rows
    if devs.size < need:
        raise ValueError(f"mesh needs {need} devices, have {devs.size}")
    return jax.sharding.Mesh(devs[:need].reshape(p_tenant, p_rows),
                             ("tenant", "data"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)


def make_tenant_update_pair(mesh, *, tenant_axis: str = "tenant",
                            axis: str = "data",
                            plan: eng.UpdatePlan = eng.DEFAULT_PLAN):
    """Fused ±sigma pair over tenant-stacked states on a 2-D mesh:
    f(L, U, v1, sigma1, v2, sigma2, m), every argument stacked on a
    leading tenant axis (L (B, M), U (B, M, M), v* (B, M), sigma* (B,),
    m (B,)).

    The tenant axis shards dim 0 and the row axis dim 1 of U, so each
    device holds a (B/P_t, M/P_r, M) brick; the body vmaps the 1-D
    collective-balanced pair over its local tenants, batching the row
    psums (still zero tenant-axis collectives, preserving the
    deadlock-free discipline).  Bucketed dispatch reads the COHORT
    ceiling max(m) on the host — one bucket rung serves the whole stack,
    mirroring ``StreamBatch``'s "max" cohort policy — and slices every
    local operand to it.
    """

    def _vpair(rows_full=None):
        def f(L, U_loc, v1, s1, v2, s2, m):
            return _rank_one_update_pair_sharded(
                L, U_loc, v1, s1, v2, s2, m, axis=axis, plan=plan,
                rows_full=rows_full)

        return jax.vmap(f)

    def fixed_body(L, U_loc, v1, s1, v2, s2, m):
        return _vpair()(L, U_loc, v1, s1, v2, s2, m)

    def sliced_body(Mb: int):
        def body(L, U_loc, v1, s1, v2, s2, m):
            R = U_loc.shape[1]
            Rb = min(R, Mb)
            Lb, Ub = _vpair(rows_full=R)(
                L[:, :Mb], U_loc[:, :Rb, :Mb], v1[:, :Rb], s1,
                v2[:, :Rb], s2, m)
            L_new = jax.vmap(lambda Lf, Lr, mm: rankone.sentinelize(
                Lf.at[:Mb].set(Lr), mm, jnp.zeros((), L.dtype)))(L, Lb, m)
            return L_new, U_loc.at[:, :Rb, :Mb].set(Ub)

        return body

    def build(Mb: int | None):
        body = fixed_body if Mb is None else sliced_body(Mb)
        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(tenant_axis), P(tenant_axis, axis),
                      P(tenant_axis, axis), P(tenant_axis),
                      P(tenant_axis, axis), P(tenant_axis), P(tenant_axis)),
            out_specs=(P(tenant_axis), P(tenant_axis, axis)),
            check_vma=False,
        ))

    if plan.dispatch != "bucketed":
        return build(None)

    cache: dict[int, object] = {}

    def dispatch(*args):
        L, m = args[0], args[-1]
        M = L.shape[1]
        Mb = eng.bucket_for(max(int(jnp.max(m)), 1), M, plan.min_bucket)
        key = Mb if Mb < M else -1
        if key not in cache:
            cache[key] = build(None if Mb >= M else Mb)
        return cache[key](*args)

    return dispatch


def make_tenant_query(mesh, spec: kf.KernelSpec, *,
                      tenant_axis: str = "tenant", plan=None):
    """Tenant-sharded snapshot queries: f(snaps, xq) -> (B, nq, C) with
    ``snaps`` a tenant-stacked ``serving.ServingSnapshot`` (every leaf
    carrying a leading B axis, e.g. from ``StreamBatch.publish``) and
    xq (B, nq, d).

    Snapshots are immutable and per-tenant independent, so the read path
    is embarrassingly parallel: the tenant axis shards every leaf's
    leading dim, the body vmaps ``serving.query`` over local tenants, and
    there are ZERO collectives — query latency never rides the update
    path's all-reduces, which is the point of decoupled serving.
    """
    from repro.core import serving

    def body(snaps, xq):
        return jax.vmap(
            lambda s, x: serving.query(s, x, spec=spec, plan=plan))(snaps,
                                                                    xq)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(tenant_axis), P(tenant_axis)),
        out_specs=P(tenant_axis), check_vma=False))


# ------------------------------------------------ row-rebalancing reshard --
def make_rebalanced_update(mesh, *, axis: str = "data",
                           plan: eng.UpdatePlan = eng.DEFAULT_PLAN):
    """Bucketed sharded update that REBALANCES small buckets across the
    mesh: f(L, U, v, sigma, m), same contract as ``make_sharded_update``.

    With m ≪ M/P the bucketed full-mesh update degenerates: only the
    devices owning global rows < M_b hold active data, yet every device
    still runs the per-bucket body — the ones past the bucket on dead
    masked rows.  Below the crossover P_eff = ceil(M_b / (M/P)) < P this
    builder re-lays the (M_b, M_b) active system out over ALL P devices
    (each getting M_b/P ACTIVE rows) and runs the 1-D sharded update on
    that balanced layout before scattering back into the full-capacity
    sharding.

    The reshard is IN-GRAPH: one jitted shard_map per bucket rung gathers
    the (M_b, M_b) active system with ``jax.lax.all_gather``, hands every
    device a balanced M_b/P slice of its rows, runs the 1-D sharded
    update on that layout, and scatters the result back into the
    full-capacity row sharding — all inside the same traced step, so the
    rebalanced update composes with scanned window blocks (the carried-
    over follow-up this closes).  Collective fan-in stays P (the psums
    still span the full mesh), but the O(M_b · m²) rotation flops now
    balance across all P devices with ZERO dead identity-row work,
    instead of piling onto the ceil(M_b/(M/P)) devices that happen to own
    low rows.  Buckets not divisible by P (and fixed dispatch, and at or
    above the bucket = capacity rung) fall back to
    ``make_sharded_update`` unchanged.
    """
    nP = mesh.shape[axis]
    full_fn = make_sharded_update(mesh, axis=axis, plan=plan)
    if plan.dispatch != "bucketed" or nP == 1:
        return full_fn

    bal_cache: dict[int, object] = {}

    def _balanced(Mb: int, M: int):
        if Mb not in bal_cache:
            R = M // nP                 # local rows, capacity layout
            Rb = Mb // nP               # local rows, balanced bucket layout
            nloc = min(R, Mb)           # local rows overlapping the bucket

            def body(L, U_local, v, sigma, m):
                p = jax.lax.axis_index(axis)
                zero = jnp.zeros((), p.dtype)
                # Gather the bucket: each device contributes its first
                # nloc rows; in device order the first Mb gathered rows
                # are exactly global rows [0, Mb) (devices past the
                # bucket contribute rows that land beyond Mb and are
                # dropped by the slice).
                U_all = jax.lax.all_gather(U_local[:nloc, :Mb], axis,
                                           tiled=True)
                Ubkt = U_all[:Mb]                       # (Mb, Mb) repl
                U_b = jax.lax.dynamic_slice(Ubkt, (p * Rb, zero), (Rb, Mb))
                v_b = jax.lax.dynamic_slice(v, (p * Rb,), (Rb,))
                Lb, U_b = _rank_one_update_sharded(L[:Mb], U_b, v_b, sigma,
                                                   m, axis=axis, plan=plan)
                # Second gather: the updated bucket, replicated, scattered
                # back into this device's capacity-layout rows.
                U_upd = jax.lax.all_gather(U_b, axis, tiled=True)  # (Mb,Mb)
                gids = jnp.arange(R) + p * R
                cand = U_upd[jnp.clip(gids, 0, Mb - 1)]
                newcols = jnp.where((gids < Mb)[:, None], cand,
                                    U_local[:, :Mb])
                L_new = rankone.sentinelize(L.at[:Mb].set(Lb), m,
                                            jnp.zeros((), L.dtype))
                return L_new, U_local.at[:, :Mb].set(newcols)

            bal_cache[Mb] = jax.jit(jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(axis, None), P(), P(), P()),
                out_specs=(P(), P(axis, None)),
                check_vma=False,
            ))
        return bal_cache[Mb]

    def dispatch(L, U, v, sigma, m):
        M = L.shape[0]
        R = M // nP
        Mb = eng.bucket_for(max(int(m), 1), M, plan.min_bucket)
        P_eff = max(1, -(-Mb // R))              # ceil(Mb / R)
        if P_eff >= nP or Mb % nP != 0:
            return full_fn(L, U, v, sigma, m)
        return _balanced(Mb, M)(L, U, v, sigma, m)

    return dispatch
