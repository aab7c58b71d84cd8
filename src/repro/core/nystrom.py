"""Incremental Nyström approximation (paper §4) — the first incremental
algorithm for the full Nyström approximation to the kernel matrix.

The landmark set grows one point at a time; the eigendecomposition of the
(unadjusted) landmark gram K_{m,m} is maintained by Algorithm 1
(``inkpca.update_unadjusted``), and the Nyström eigenpairs of the full n×n
kernel matrix follow from the Williams–Seeger rescaling (paper eq. 7):

    Λ_nys = (n/m) Λ,        U_nys = sqrt(m/n) K_{n,m} U Λ^{-1}

so that  K̃ = U_nys Λ_nys U_nys^T = K_{n,m} K_{m,m}^{-1} K_{m,n}.

The O(n m^2) reconstruction hot spot  B diag(1/Λ) B^T  (B = K_{n,m} U) is
implemented by the fused Pallas kernel ``repro.kernels.nystrom_recon``.

This enables *empirical* stopping: monitor the chosen norm of K - K̃ (or a
cheap proxy) after each added landmark and stop when it plateaus.

For landmark sets that grow far below capacity, construct an
``engine.Engine`` over this module with
``UpdatePlan(dispatch="bucketed")``: ``Engine.add_landmark`` wraps this
module's ``add_landmark`` with bucketed dispatch so each addition costs
O(M_b³) at the active power-of-two bucket M_b instead of O(M³) at
capacity.  (Landmark streams also ride the composed ``Engine.step``
pipeline via ``offer_landmark``/``add_landmark`` — the stage selection
in ``step`` is orthogonal to which state family the ingest touches.)

Two row regimes:

* **Fixed rows** (default): the full dataset ``x_all`` is known upfront
  and ``Knm`` is allocated dense (n, M).
* **Growing rows** (``init_nystrom(..., grow_rows=True)``): the stream is
  open-ended, so ``Knm`` starts at the seed landmarks' rows and
  ``observe_rows`` appends a row block per observed (non-landmark) point —
  memory tracks the observed stream instead of paying n upfront.  The
  observed points are carried in ``NystromState.Xrows`` so later
  ``add_landmark`` calls can fill the new landmark's column; pass
  ``x_all=None`` in this mode.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import engine as eng
from repro.core import inkpca, kernels_fn as kf, rankone
from repro.core.precision import MATMUL_PRECISION

Array = jax.Array


class NystromState(NamedTuple):
    kpca: inkpca.KPCAState   # eigendecomposition of K_{m,m} (unadjusted)
    Knm: Array               # (n, M) columns k(X_rows, x_j) for landmarks j<m
    Xrows: Array | None = None   # (n, d) observed row points (grow_rows mode)


def init_nystrom(x_all: Array | None, x0: Array, capacity: int,
                 spec: kf.KernelSpec, *, dtype=jnp.float32,
                 grow_rows: bool = False) -> NystromState:
    kpca = inkpca.init_state(x0, capacity, spec, adjusted=False, dtype=dtype)
    x0 = x0.astype(dtype)
    if grow_rows:
        if x_all is not None:
            raise ValueError("grow_rows=True derives rows from the stream; "
                             "pass x_all=None and call observe_rows")
        x_rows = x0              # landmarks are observed points too
    else:
        if x_all is None:
            raise ValueError("x_all is required unless grow_rows=True")
        x_rows = x_all.astype(dtype)
    n = x_rows.shape[0]
    Knm = jnp.zeros((n, capacity), dtype)
    cols = kf.gram_block(x_rows, x0, spec=spec)
    Knm = Knm.at[:, : x0.shape[0]].set(cols.astype(dtype))
    return NystromState(kpca=kpca, Knm=Knm,
                        Xrows=x_rows if grow_rows else None)


def observe_rows(state: NystromState, xb: Array,
                 spec: kf.KernelSpec, *,
                 plan: eng.UpdatePlan | None = None) -> NystromState:
    """Append a block of observed (non-landmark) points as new Knm rows.

    Only valid in ``grow_rows`` mode.  Row growth is a host-level concat
    (each distinct row count is a new shape), so feed points in batches —
    the kernel block itself is one fused device call.  Under a bucketed
    ``plan.fuse_krow`` the gram is evaluated only against the active
    landmark bucket (columns beyond it are zero by the masking anyway),
    so the call costs O(b·M_b·d) instead of O(b·M·d).

    With ``plan.health`` quarantine enabled, non-finite observed points
    are dropped before any Knm row is built (row growth is host-level
    already, so the filter costs nothing extra): a NaN row would
    otherwise poison every later trace-error contraction.  The caller
    sees the rejection in the returned row count (``Xrows.shape[0]``);
    the serving loop surfaces it as a quarantine counter.
    """
    if state.Xrows is None:
        raise ValueError("observe_rows needs a grow_rows=True state")
    dtype = state.Knm.dtype
    xb = jnp.atleast_2d(xb).astype(dtype)
    policy = getattr(plan, "health", None) if plan is not None else None
    if policy is not None and policy.quarantine:
        import numpy as np
        keep = np.isfinite(np.asarray(xb)).all(axis=1)
        if not keep.all():
            xb = xb[jnp.asarray(keep)]
            if xb.shape[0] == 0:
                return state
    M = state.Knm.shape[1]
    if (plan is not None and plan.fuse_krow
            and plan.dispatch == "bucketed"):
        Mb = eng.bucket_for(max(int(state.kpca.m), 1), M, plan.min_bucket)
    else:
        Mb = M
    mask = rankone.active_mask(Mb, state.kpca.m)
    rows_b = kf.gram_block(xb, state.kpca.X[:Mb], spec=spec).astype(dtype)
    rows_b = jnp.where(mask[None, :], rows_b, 0.0)
    rows = (rows_b if Mb == M
            else jnp.zeros((xb.shape[0], M), dtype).at[:, :Mb].set(rows_b))
    return state._replace(Knm=jnp.concatenate([state.Knm, rows], axis=0),
                          Xrows=jnp.concatenate([state.Xrows, xb], axis=0))


@partial(jax.jit, static_argnames=("spec", "plan"))
def add_landmark(state: NystromState, x_all: Array | None, x_new: Array,
                 spec: kf.KernelSpec, *,
                 plan: eng.UpdatePlan = eng.DEFAULT_PLAN) -> NystromState:
    """Grow the landmark set by one point (streaming-compatible).

    In ``grow_rows`` mode the new column is evaluated against the observed
    rows carried in the state (``x_all`` must be None); add the point via
    ``observe_rows`` first if it should also appear as a row.

    ``plan.fuse_krow`` routes the eigensystem growth through the fused
    kernel-row + projection prologue (``engine._ingest``) — the same
    single-pass-over-U ingest the KPCA stream uses.
    """
    m = state.kpca.m
    kpca = eng._ingest(state.kpca, x_new, spec, False, plan)
    x_rows = state.Xrows if state.Xrows is not None else x_all
    col = kf.kernel_row(x_new, x_rows.astype(state.Knm.dtype), spec=spec)
    zero = jnp.zeros((), m.dtype)
    Knm = jax.lax.dynamic_update_slice(state.Knm, col[:, None].astype(state.Knm.dtype),
                                       (zero, m))
    return state._replace(kpca=kpca, Knm=Knm)


@partial(jax.jit, static_argnames=("spec", "plan"))
def remove_landmark(state: NystromState, j: Array, spec: kf.KernelSpec, *,
                    plan: eng.UpdatePlan = eng.DEFAULT_PLAN) -> NystromState:
    """Shrink the landmark set by one point — the paper's admission loop
    made reversible.

    The eigensystem of K_{m,m} is downdated by the inverse ±sigma pair
    (``downdate.downdate_unadjusted``, the exact inverse of Algorithm 1);
    the Knm columns follow the same survivor-order-preserving permutation
    the downdate applies to the landmark rows, and the evicted landmark's
    column is zeroed.  Observed rows (``Xrows``/Knm rows) are untouched —
    an ex-landmark remains an observed point.
    """
    from repro.core import downdate as dd

    kpca = dd.permute_to_boundary(state.kpca, j)
    order = dd.boundary_perm(j, state.kpca.m, state.kpca.L.shape[0])
    q = state.kpca.m - 1
    Knm = state.Knm[:, order]
    Knm = Knm.at[:, q].set(jnp.zeros((Knm.shape[0],), Knm.dtype))
    kpca = dd.downdate_unadjusted(kpca, spec, plan=plan)
    return state._replace(kpca=kpca, Knm=Knm)


def replace_landmark(state: NystromState, x_all: Array | None, j: Array,
                     x_new: Array, spec: kf.KernelSpec, *,
                     plan: eng.UpdatePlan = eng.DEFAULT_PLAN
                     ) -> NystromState:
    """Swap landmark ``j`` for ``x_new``: remove + add.

    O(m³) eigensystem work plus ONE new Knm column (O(n) kernel evals)
    versus the O(n·m·d) gram rebuild + eigh of a from-scratch recompute —
    see ``benchmarks/bench_window.py`` for the measured gap.  Use
    ``engine.Engine.replace_landmark`` for the bucketed spelling.
    """
    state = remove_landmark(state, jnp.asarray(j, jnp.int32), spec,
                            plan=plan)
    return add_landmark(state, x_all, x_new, spec, plan=plan)


# ------------------------------------------------- landmark admission ----
def leverage_scores(state: NystromState, reg: float = 1e-6) -> Array:
    """Ridge leverage score of each landmark under the maintained
    eigendecomposition: l_j = Σ_k U[j,k]² λ_k/(λ_k + reg·tr/m).

    The regularizer is scaled by the mean active eigenvalue so ``reg``
    is dimensionless.  Low-leverage landmarks are the redundant ones —
    the replacement victims of the "leverage" admission policy
    (leverage-style subset quality scoring follows Sterge &
    Sriperumbudur, 2105.08875).
    """
    st = state.kpca
    M = st.L.shape[0]
    mask = rankone.active_mask(M, st.m)
    lam = jnp.where(mask, st.L, 0.0)
    lam_bar = jnp.sum(lam) / jnp.maximum(st.m.astype(st.L.dtype), 1.0)
    lam_reg = jnp.maximum(reg * lam_bar, jnp.finfo(st.L.dtype).tiny)
    w = jnp.where(mask, lam / (lam + lam_reg), 0.0)
    scores = jnp.sum(st.U**2 * w[None, :], axis=1)
    return jnp.where(mask, scores, 0.0)


def admission_residual(state: NystromState, x: Array,
                       spec: kf.KernelSpec) -> Array:
    """Projection residual of a candidate landmark onto the current
    landmark span: δ(x) = k(x,x) − b(x)ᵀ K_{m,m}⁺ b(x) ≥ 0.

    This is the Schur complement of the candidate against the landmark
    gram — exactly the marginal the incremental Nyström approximation
    gains by admitting x (δ = 0 means x is already spanned).  O(m²) per
    candidate from the maintained eigenpairs; no n×n object is formed.
    """
    st = state.kpca
    mask = rankone.active_mask(st.L.shape[0], st.m)
    b, k_xx = eng.masked_row(st, x, spec)
    y = jnp.matmul(st.U.T, b, precision=MATMUL_PRECISION)
    return k_xx - jnp.sum(_pinv_lam(st.L, mask) * y * y)


def _rows_are_landmarks(state: NystromState, spec: kf.KernelSpec) -> bool:
    """Do the stored landmark points coincide with the observed rows, in
    order?  Verified by rebuilding the maintained K_{n,m} columns from
    the stored points and comparing — O(n·m·d), the cost of one Knm
    column rebuild, and the only evidence available once ``x_all`` is
    gone.  A count match alone is NOT enough: ``add_landmark`` accepts
    points from outside the observed rows.
    """
    st = state.kpca
    n = state.Knm.shape[0]
    m = int(st.m)
    G = kf.gram_block(st.X[:n].astype(st.L.dtype), st.X[:m],
                      spec=spec).astype(state.Knm.dtype)
    scale = float(jnp.max(jnp.abs(G))) + 1e-30
    err = float(jnp.max(jnp.abs(state.Knm[:, :m] - G)))
    return err <= 1e-5 * scale


def trace_error(state: NystromState, spec: kf.KernelSpec,
                x_all: Array | None = None) -> Array:
    """Trace-norm of K − K̃ over the observed rows, incrementally.

    For Nyström, K − K̃ is PSD, so the trace norm is the exact trace gap
    Σ_i (k(x_i,x_i) − K̃_ii) — computable in O(n·m) from the maintained
    eigenpairs without ever forming the n×n difference the offline
    ``approximation_error`` needs.  This is the quantity whose plateau
    the sufficient-subset stopping rule watches (the paper's headline
    "empirical evaluation of when a subset of sufficient size has been
    obtained", made online).
    """
    st = state.kpca
    x_rows = state.Xrows if state.Xrows is not None else x_all
    n = state.Knm.shape[0]
    if x_rows is not None:
        diag_k = kf.kernel_diag(x_rows.astype(st.L.dtype), spec=spec)
    elif kf.constant_diag(spec) is not None:
        # Stationary kernels have an input-independent diagonal — the row
        # points only ever feed Σ_i k(x_i, x_i), so nothing is lost.
        diag_k = jnp.full((n,), kf.constant_diag(spec), st.L.dtype)
    elif n == int(st.m) and _rows_are_landmarks(state, spec):
        # The stored landmark points cover the observed stream (verified
        # against the maintained Knm, not just the row count — landmarks
        # admitted from OUTSIDE the observed rows must keep raising).
        diag_k = kf.kernel_diag(st.X[:n].astype(st.L.dtype), spec=spec)
    else:
        raise ValueError(
            "trace_error is underdetermined: fixed-row state without "
            "x_all, a non-constant-diagonal kernel, and observed rows "
            "not covered by the stored landmarks — pass x_all")
    mask = rankone.active_mask(st.L.shape[0], st.m)
    B = jnp.matmul(state.Knm, jnp.where(mask[None, :], st.U, 0.0),
                   precision=MATMUL_PRECISION)
    diag_tilde = jnp.sum(B**2 * _pinv_lam(st.L, mask)[None, :], axis=1)
    return jnp.sum(diag_k - diag_tilde)


def admission_trace_delta(state: NystromState, x: Array,
                          spec: kf.KernelSpec,
                          x_all: Array | None = None
                          ) -> tuple[Array, Array]:
    """Exact decrease of ``trace_error`` from admitting ``x`` as a
    landmark — O(n·m), against the O(n·m²) full recompute.

    Admitting x borders the landmark gram with (b, k_xx) and appends the
    column c = k(X_rows, x); by the block-inverse (Schur complement)
    identity the Nyström reconstruction gains exactly one PSD rank-one
    term:

        K̃' = K̃ + r rᵀ / δ,      r = K_nm K_mm⁺ b − c,

    with δ = k_xx − bᵀ K_mm⁺ b the admission residual.  The trace gap
    therefore drops by exactly Σ_i r_i² / δ.  Returns ``(delta,
    residual)``; delta is clamped to 0 when δ is numerically zero (the
    candidate is already spanned, nothing to gain).
    """
    st = state.kpca
    x = jnp.asarray(x)
    x_rows = state.Xrows if state.Xrows is not None else x_all
    if x_rows is None:
        raise ValueError("admission_trace_delta needs the observed rows "
                         "(grow_rows state or x_all)")
    mask = rankone.active_mask(st.L.shape[0], st.m)
    b, k_xx = eng.masked_row(st, x, spec)
    y = jnp.matmul(st.U.T, b, precision=MATMUL_PRECISION)
    alpha = _pinv_lam(st.L, mask) * y          # K_mm⁺ b in the eigenbasis
    delta_res = k_xx - jnp.sum(y * alpha)
    c = kf.kernel_row(x, x_rows.astype(st.L.dtype), spec=spec)
    w = jnp.matmul(st.U, alpha, precision=MATMUL_PRECISION)
    r = jnp.matmul(state.Knm, w, precision=MATMUL_PRECISION) - c
    tol = jnp.finfo(st.L.dtype).eps * jnp.maximum(k_xx, 1.0)
    delta = jnp.where(delta_res > tol,
                      jnp.sum(r * r) / jnp.maximum(delta_res, tol), 0.0)
    return delta, delta_res


@jax.jit
def removal_trace_delta(state: NystromState, j: Array
                        ) -> tuple[Array, Array]:
    """Exact increase of ``trace_error`` from removing landmark ``j`` —
    O(n·m) from the maintained eigenpairs.

    Deleting row/column j from the landmark gram is the reverse bordering
    of ``admission_trace_delta``: with W = K_mm⁺ = U diag(λ⁺) Uᵀ and
    w = W e_j, the block-inverse identity gives

        K̃_minus = K_nm (W − w wᵀ / W_jj) K_nm^T,

    (the deflated matrix has zero j-th row/column, so the dropped Knm
    column is inert) and the trace gap grows by exactly
    Σ_i (K_nm w)_i² / W_jj.  Returns ``(inc, W_jj)``; W_jj ≤ 0 (victim
    support entirely in deflated directions) means the leave-one-out
    inverse does not exist — callers should fall back to an exact resync.
    """
    st = state.kpca
    mask = rankone.active_mask(st.L.shape[0], st.m)
    pinv = _pinv_lam(st.L, mask)
    uj = st.U[j, :]
    w = jnp.matmul(st.U, pinv * uj, precision=MATMUL_PRECISION)
    Wjj = jnp.sum(uj * uj * pinv)
    t = jnp.matmul(state.Knm, w, precision=MATMUL_PRECISION)
    safe = jnp.maximum(Wjj, jnp.finfo(st.L.dtype).tiny)
    return jnp.sum(t * t) / safe, Wjj


@partial(jax.jit, static_argnames=("spec",))
def swap_trace_delta(state: NystromState, j: Array, x: Array,
                     spec: kf.KernelSpec, x_all: Array | None = None
                     ) -> tuple[Array, Array]:
    """Exact net change of ``trace_error`` from replacing landmark ``j``
    with ``x`` — O(n·m), no leave-one-out eigensystem ever formed.

    Composes the two block-inverse identities from the PRE-swap state:
    removal adds Σ(K_nm w)²/W_jj (``removal_trace_delta``), then the
    admission against the DEFLATED inverse A = W − w wᵀ/W_jj subtracts
    Σ r²/δ' with b̃ the candidate's kernel row zeroed at the victim slot,
    δ' = k_xx − b̃ᵀAb̃ and r = K_nm A b̃ − c.  Returns ``(net, W_jj)``
    (net = inc − dec, to be ADDED to the tracked value); W_jj ≤ 0 or a
    non-finite net means fall back to resync.
    """
    st = state.kpca
    x = jnp.asarray(x)
    x_rows = state.Xrows if state.Xrows is not None else x_all
    if x_rows is None:
        raise ValueError("swap_trace_delta needs the observed rows "
                         "(grow_rows state or x_all)")
    dtype = st.L.dtype
    mask = rankone.active_mask(st.L.shape[0], st.m)
    pinv = _pinv_lam(st.L, mask)
    tiny = jnp.finfo(dtype).tiny

    uj = st.U[j, :]
    w = jnp.matmul(st.U, pinv * uj, precision=MATMUL_PRECISION)  # W e_j
    Wjj = jnp.maximum(jnp.sum(uj * uj * pinv), tiny)
    t = jnp.matmul(state.Knm, w, precision=MATMUL_PRECISION)
    inc = jnp.sum(t * t) / Wjj

    b, k_xx = eng.masked_row(st, x, spec)
    bt = b.at[j].set(0.0)                      # row vs SURVIVING landmarks
    Wb = jnp.matmul(st.U, pinv * jnp.matmul(st.U.T, bt,
                                            precision=MATMUL_PRECISION),
                    precision=MATMUL_PRECISION)
    Ab = Wb - w * (jnp.dot(w, bt, precision=MATMUL_PRECISION) / Wjj)  # A b̃
    delta_res = k_xx - jnp.dot(bt, Ab, precision=MATMUL_PRECISION)
    c = kf.kernel_row(x, x_rows.astype(dtype), spec=spec)
    r = jnp.matmul(state.Knm, Ab, precision=MATMUL_PRECISION) - c
    tol = jnp.finfo(dtype).eps * jnp.maximum(k_xx, 1.0)
    dec = jnp.where(delta_res > tol,
                    jnp.sum(r * r) / jnp.maximum(delta_res, tol), 0.0)
    return inc - dec, jnp.sum(uj * uj * pinv)


class TraceErrorTracker:
    """Maintains the sufficient-subset error metric incrementally across
    the landmark lifecycle (ROADMAP PR-4 follow-up).

    The stopping rule watches ``trace_error`` after every admission, and
    recomputing it exactly costs O(n·m²) (the ``Knm @ U`` contraction) —
    the dominant per-offer cost of the leverage policy once n is large.
    This tracker keeps the value current from O(n·m) increments instead:

    * ``observe(state, x)`` — a newly observed row adds its own
      projection residual δ(x) to the trace gap (O(m²); call once per
      ``observe_rows`` point, before or after — the residual only reads
      the landmark eigensystem).
    * ``admitted(state_before, x)`` — subtract
      ``admission_trace_delta(state_before, x)``; ``state_before`` is
      the state the candidate was offered to (rows already observed).
    * ``replaced(state_after, state_before=..., x=...)`` — apply the
      O(n·m) ``swap_trace_delta`` computed from the pre-swap state: the
      leave-one-out inverse comes from the maintained eigenpairs via the
      block-inverse identity, so a swap no longer forces the O(n·m²)
      exact resync.  The victim index defaults to the lowest-leverage
      landmark (the ``consider_landmark`` choice); pass ``j=`` to
      override.  Degenerate victims (W_jj ≤ 0) or a non-finite delta
      fall back to the exact resync, as does calling with only
      ``state_after`` (the legacy spelling).
    * every ``resync_every`` admissions/swaps the value re-anchors to
      the exact recompute, bounding float drift on unbounded lifecycles
      (the drift itself is regression-tested against the recompute).
    """

    def __init__(self, state: NystromState, spec: kf.KernelSpec, *,
                 x_all: Array | None = None, resync_every: int = 64):
        self.spec = spec
        self.x_all = x_all
        self.resync_every = int(resync_every)
        self.value = float(trace_error(state, spec, x_all))
        self._admits = 0
        self._pending_resync = False

    def resync(self, state: NystromState) -> float:
        self.value = float(trace_error(state, self.spec, self.x_all))
        self._admits = 0
        self._pending_resync = False
        return self.value

    def observe(self, state: NystromState, x: Array,
                residual: float | None = None) -> float:
        """Pass ``residual`` when the caller already computed
        ``admission_residual`` for this point (the serving loop offers
        the same point next — one dispatch instead of two)."""
        if residual is None:
            residual = float(admission_residual(state, jnp.asarray(x),
                                                self.spec))
        self.value += max(float(residual), 0.0)
        return self.value

    def admitted(self, state_before: NystromState, x: Array) -> float:
        delta, _ = admission_trace_delta(state_before, x, self.spec,
                                         self.x_all)
        self.value = max(self.value - float(delta), 0.0)
        self._count_increment()
        return self.value

    def replaced(self, state_after: NystromState, *,
                 state_before: NystromState | None = None,
                 x: Array | None = None, j: int | None = None) -> float:
        import math

        import numpy as np

        if state_before is None or x is None:
            return self.resync(state_after)       # legacy exact spelling
        if j is None:
            m = int(state_before.kpca.m)
            j = int(np.argmin(np.asarray(
                leverage_scores(state_before)[:m])))
        net, Wjj = swap_trace_delta(state_before,
                                    jnp.asarray(j, jnp.int32),
                                    jnp.asarray(x), self.spec, self.x_all)
        net, Wjj = float(net), float(Wjj)
        if not math.isfinite(net) or Wjj <= 0.0:
            return self.resync(state_after)
        self.value = max(self.value + net, 0.0)
        self._count_increment()
        return self.value

    def _count_increment(self) -> None:
        self._admits += 1
        if self.resync_every and self._admits >= self.resync_every:
            # Re-anchoring needs the POST-event state; callers hand us the
            # pre-state, so defer to the next lifecycle event instead of
            # recomputing on a stale snapshot.
            self._admits = 0
            self._pending_resync = True

    def maybe_resync(self, state: NystromState) -> float:
        """Honor a pending periodic re-anchor (call with the CURRENT
        state after the lifecycle event that tripped it)."""
        if self._pending_resync:
            return self.resync(state)
        return self.value


class SufficientSubsetRule:
    """Online stopping rule for landmark admission (paper §4 made online).

    Feed the error trend (``trace_error`` after each admitted landmark);
    the subset is declared sufficient once the *relative* improvement has
    stayed below ``rel_tol`` for ``patience`` consecutive admissions —
    the plateau of the paper's Fig. 2 curves, detected without a
    reference spectrum.
    """

    def __init__(self, rel_tol: float = 1e-2, patience: int = 3):
        self.rel_tol = float(rel_tol)
        self.patience = int(patience)
        self.history: list[float] = []
        self._flat = 0

    @property
    def sufficient(self) -> bool:
        return self._flat >= self.patience

    def observe(self, err) -> bool:
        """Record one error value; returns True once sufficient."""
        err = float(err)
        if self.history:
            prev = self.history[-1]
            rel = (prev - err) / max(abs(prev), 1e-30)
            self._flat = self._flat + 1 if rel < self.rel_tol else 0
        self.history.append(err)
        return self.sufficient


def consider_landmark(engine, state: NystromState, x: Array, *,
                      x_all: Array | None = None,
                      budget: int | None = None,
                      admit_tol: float = 1e-3,
                      reg: float = 1e-6,
                      min_rows: int = 0,
                      residual: float | None = None
                      ) -> tuple[NystromState, str]:
    """Leverage-policy admission of one candidate landmark.

    Decision ladder (returns the new state and what happened):

    * residual δ(x) ≤ admit_tol · k(x,x): already spanned — "rejected".
    * below ``budget`` landmarks: "admitted" (bucketed add).
    * at budget: find the lowest-leverage landmark; if its leverage is
      below the candidate's normalized residual, swap — "replaced";
      otherwise "rejected".

    ``engine`` is an ``engine.Engine`` (adjusted=False) so every path
    runs at bucket capacity; drive it from a ``SufficientSubsetRule`` to
    stop offering candidates altogether.  ``residual`` short-circuits
    the O(m²) ``admission_residual`` dispatch when the caller already
    has it (e.g. a ``TraceErrorTracker.observe`` on the same point).
    """
    import numpy as np

    M = state.kpca.L.shape[0]
    m = int(state.kpca.m)
    budget = budget if budget is not None else M - 1
    delta = (float(residual) if residual is not None
             else float(admission_residual(state, jnp.asarray(x),
                                           engine.spec)))
    k_xx = float(kf.kernel_diag(jnp.asarray(x)[None].astype(state.kpca.L.dtype),
                                spec=engine.spec)[0])
    gain = delta / max(k_xx, 1e-30)
    if gain <= admit_tol:
        return state, "rejected"
    if m < budget:
        return engine.add_landmark(state, x_all, x, min_rows=min_rows), \
            "admitted"
    lev = np.asarray(leverage_scores(state, reg=reg)[:m])
    victim = int(np.argmin(lev))
    if float(lev[victim]) < gain:
        return engine.replace_landmark(state, x_all, victim, x,
                                       min_rows=min_rows), "replaced"
    return state, "rejected"


def _pinv_lam(L: Array, mask: Array) -> Array:
    """Pseudo-inverse of the active spectrum: exact/near-zero eigenvalues
    (a compacted rank-truncated state carries rank-deficient active pairs)
    deflate to 0 instead of amplifying to 1/0."""
    tol = (L.shape[0] * jnp.finfo(L.dtype).eps
           * jnp.max(jnp.where(mask, jnp.abs(L), 0.0)))
    ok = mask & (jnp.abs(L) > tol)
    return jnp.where(ok, 1.0 / jnp.where(ok, L, 1.0), 0.0)


def nystrom_eigpairs(state: NystromState, n: int) -> tuple[Array, Array]:
    """Approximate eigenpairs of the full K via the rescaling (paper eq. 7)."""
    st = state.kpca
    M = st.L.shape[0]
    mask = rankone.active_mask(M, st.m)
    mf = st.m.astype(st.L.dtype)
    lam_nys = jnp.where(mask, (n / mf) * st.L, 0.0)
    U_nys = jnp.sqrt(mf / n) * jnp.matmul(
        state.Knm, st.U * _pinv_lam(st.L, mask)[None, :],
        precision=MATMUL_PRECISION)
    U_nys = jnp.where(mask[None, :], U_nys, 0.0)
    return lam_nys, U_nys


def query_features(state: NystromState, xq: Array, n: int,
                   spec: kf.KernelSpec, *,
                   plan: eng.UpdatePlan | None = None) -> Array:
    """Nyström eigenvector rows for OUT-OF-SAMPLE query points:
    sqrt(m/n) · k(x_q, X_lm) U Λ⁺ — the ``nystrom_eigpairs`` rescaling
    (paper eq. 7) evaluated at new points, e.g. to extend K̃ to a query
    batch via ``U_q Λ_nys U_nysᵀ``.

    Under ``plan.fuse_krow`` the query gram never materializes: the fused
    ``nystrom_recon.transform_project`` kernel (shared with the KPCA
    batched transform) contracts each kernel tile against
    S = U diag(λ⁺) in VMEM.

    Publish-then-query (``publish_features``, ``snapshot_features``): the
    compiled serving head answers against an ephemeral snapshot, so the
    features of a state are bit-identical to those of a snapshot
    published from it.
    """
    return snapshot_features(publish_features(state, n), xq, spec,
                             plan=plan)


def publish_features(state: NystromState, n: int, *,
                     generation: int | Array = 0):
    """Freeze the out-of-sample feature head (``query_features``) into a
    ``serving.ServingSnapshot``: S = sqrt(m/n)·U·lam⁺ precomputed at
    publication, so serving-time Nyström features are plain snapshot
    queries against the frozen landmark set — immutable under concurrent
    landmark lifecycle updates to the working state."""
    from repro.core import serving

    st = state.kpca
    mask = rankone.active_mask(st.L.shape[0], st.m)
    mf = st.m.astype(st.L.dtype)
    s_mat = (jnp.sqrt(mf / n)
             * (st.U * _pinv_lam(st.L, mask)[None, :])).astype(st.X.dtype)
    return serving.ServingSnapshot(
        S=s_mat, X=st.X, m=st.m, affine=None,
        generation=jnp.asarray(generation, jnp.int32))


def snapshot_features(snap, xq: Array, spec: kf.KernelSpec, *,
                      plan: eng.UpdatePlan | None = None) -> Array:
    """Nyström eigenvector rows at query points for a published snapshot
    ((nq, d) -> (nq, M); columns >= m are zero)."""
    from repro.core import serving

    return serving.query(snap, xq, spec=spec, plan=plan)


def reconstruct_tilde(state: NystromState, *, use_pallas: bool = False) -> Array:
    """K̃ = K_{n,m} K_{m,m}^{-1} K_{m,n} via the maintained eigenpairs."""
    st = state.kpca
    M = st.L.shape[0]
    mask = rankone.active_mask(M, st.m)
    B = jnp.matmul(state.Knm, jnp.where(mask[None, :], st.U, 0.0),
                   precision=MATMUL_PRECISION)   # (n, M)
    inv_lam = _pinv_lam(st.L, mask)
    if use_pallas:
        from repro.kernels.nystrom_recon import ops as _ops
        return _ops.scaled_gram(B, inv_lam)
    return jnp.matmul(B * inv_lam[None, :], B.T, precision=MATMUL_PRECISION)


@dataclass
class ErrorNorms:
    fro: float
    spectral: float
    trace: float


def approximation_error(K: Array, K_tilde: Array) -> ErrorNorms:
    """Frobenius / spectral / trace norms of K - K̃ (paper Fig. 2 metrics)."""
    D = K - K_tilde
    fro = jnp.linalg.norm(D)
    ev = jnp.linalg.eigvalsh(D)            # D symmetric
    spectral = jnp.max(jnp.abs(ev))
    trace = jnp.sum(jnp.abs(ev))
    return ErrorNorms(fro=float(fro), spectral=float(spectral),
                      trace=float(trace))
