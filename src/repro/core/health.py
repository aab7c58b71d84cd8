"""Self-healing layer: in-graph health probes, input quarantine, heal ladder.

The rank-one eigendecomposition updates (paper Algorithms 1–2) are exact
in theory but accumulate floating-point error over unbounded streams, and
a single non-finite input poisons ``U`` forever.  This module gives every
consumer (stream, window scan, multi-tenant batch, Nyström tracker,
sharded mesh, serving loop) three things:

**In-graph probes** (``probe``) — the exact orthogonality residual
over every column, eigenvalue-negativity and non-finite flags, computed
INSIDE the existing update/window dispatches.  A ``HealthState`` pytree
rides along the ``KPCAState`` exactly the way the arrival ring rides
``WindowState``: no extra host sync, no extra dispatch.  The residual
costs one M³ contraction (UᵀU) per dispatch, against the ~8 M³ of every
point a dispatch ingests: at most an eighth of a single-point dispatch,
1/(8n) of an n-point block.  A sampled probe would be cheaper but blind:
rounding drift is spread over all columns, and a few sampled ones can
sit under the threshold while others are over it.

**Input quarantine** (``_gate`` inside the guarded dispatches) — a
non-finite (or, optionally, kernel-row-outlier) point is rejected BEFORE
the rank-one pair fires.  The rejection is spelled sanitize + per-leaf
``jnp.where`` select, NOT ``lax.cond``: the update body executes
unconditionally on a sanitized stand-in (the stored seed row), and the
select discards it.  That keeps the collective schedule of the scanned
window block and the sharded paths FIXED (the same deadlock-free
discipline as the merge fallback — see ``core/distributed.py``), works
identically under vmap, and makes a rejected step return the prior state
bitwise (``where(False, new, old)`` copies ``old``'s bits; the guarded
dispatches additionally select at the FULL state so bucketed
scatter-sentinel regeneration cannot perturb a rejected step either).

**The heal ladder** (``heal_kpca`` / ``Engine.heal``) — escalation:

    polish   — QR re-orthonormalization of the eigenvector block;
               eigenvalues untouched.  O(M³) but heals only the loss of
               orthogonality; preserves the padding invariants exactly
               (active columns vanish on rows ≥ m, so Gram–Schmidt never
               mixes mass into the inactive identity columns).
    resync   — exact re-diagonalization from the stored active points,
               mirroring ``inkpca.init_state`` (gram, optional centering,
               eigh): post-heal state matches batch KPCA of the same
               window by construction.  Also rebuilds S/K1 bookkeeping.
    restore  — the stored points themselves are corrupt: raise
               ``HealthError`` so the caller reloads the last checkpoint
               (``checkpoint/npz_store.load_checkpoint``), whose
               crash-atomicity the fault suite now actually tests.

``level="auto"`` walks the ladder from the cheapest rung that the exact
(host-side, O(M²·m)) residual says will work.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import engine as eng
from repro.core import kernels_fn as kf
from repro.core import rankone
from repro.core.precision import MATMUL_PRECISION

Array = jax.Array


class HealthError(RuntimeError):
    """Raised when in-place healing cannot proceed (restore rung): the
    stored points are themselves corrupt, so the only exact recovery is
    reloading the last good checkpoint."""


class HealthPolicy(NamedTuple):
    """Plan-level health configuration — hashable, so it can ride
    ``UpdatePlan.health`` as a jit-static field (like ``window`` and
    ``landmark_policy``).

    orth_tol:    healthy-threshold on the orthogonality residual
                 max_j ‖(UᵀU − I) e_j‖₂ — crossing it is the heal trigger
    neg_tol:     relative eigenvalue-negativity tolerance: the gram (or
                 centered gram) is PSD, so min(L) < −neg_tol·max|L| flags
                 corruption.  Small negatives near 0 are normal f32
                 noise — centering deflates one dimension to a slightly
                 negative eigenvalue that healthy adjusted streams carry
                 at up to ~2e-3·max|L| when the spectrum is small — so
                 the default stays well above that floor while still
                 flagging genuinely negative eigenvalues (corruption
                 shows relative negativity near 1)
    quarantine:  reject non-finite inputs in-graph (zero state mutation)
    outlier_tol: kernel-row outlier gate — reject a point whose masked
                 kernel row carries almost no mass against the stored
                 points: max_i|a_i| < outlier_tol·k(x,x).  0 disables
                 (linear kernels can have legitimately tiny rows).
    polish_max:  largest exact residual ``heal(level='auto')`` still
                 hands to the cheap polish rung; beyond it (or when
                 eigenvalues are implicated) auto escalates to resync
    drift_tol:   staleness-aware publication threshold: relative L2
                 drift of the working top-C spectrum vs the spectrum
                 frozen into the front snapshot that triggers a republish
                 (``launch/serve.IngestServeLoop``)
    """

    orth_tol: float = 1e-3
    neg_tol: float = 1e-2
    quarantine: bool = True
    outlier_tol: float = 0.0
    polish_max: float = 1e-2
    drift_tol: float = 0.05


DEFAULT_POLICY = HealthPolicy()


class HealthState(NamedTuple):
    """Probe results + quarantine counters — a small pytree of scalars
    that rides along the eigensystem state through the guarded
    dispatches (device-resident; reading it is the caller's sync).

    orth_err:      last orthogonality residual max_j ‖(UᵀU − I) e_j‖₂
    neg_frac:      relative negativity of the most negative active
                   eigenvalue, max(0, −min L)/max|L| (0 when PSD holds)
    nonfinite:     sticky flag: 1 once any probe saw a non-finite
                   eigenvalue/eigenvector entry (cleared by ``heal``)
    quarantined:   points rejected by the input gate so far
    rejected_last: 1 iff the MOST RECENT offered point was rejected
    probes:        probe dispatch counter (drives column rotation)
    spec_drift:    relative top-C spectral drift vs. the reference
                   spectrum of the last published snapshot; −1 when no
                   reference has been folded in yet
    """

    orth_err: Array
    neg_frac: Array
    nonfinite: Array
    quarantined: Array
    rejected_last: Array
    probes: Array
    spec_drift: Array


def init_health(dtype=jnp.float32) -> HealthState:
    z = jnp.zeros((), dtype)
    zi = jnp.zeros((), jnp.int32)
    return HealthState(orth_err=z, neg_frac=z, nonfinite=zi, quarantined=zi,
                       rejected_last=zi, probes=zi,
                       spec_drift=jnp.asarray(-1.0, dtype))


# ------------------------------------------------------------- probes --
def top_spectrum(state, C: int) -> Array:
    """Descending top-C active eigenvalues, zero-padded past m (traced)."""
    M = state.L.shape[0]
    mask = rankone.active_mask(M, state.m)
    order = jnp.argsort(jnp.where(mask, -state.L, jnp.inf))
    lam = state.L[order[:C]]
    return jnp.where(jnp.arange(C) < state.m, lam, 0.0)


def spectral_drift(state, ref_lam: Array) -> Array:
    """Relative L2 distance of the working top-C spectrum from a frozen
    reference — the staleness signal for drift-triggered publication."""
    cur = top_spectrum(state, ref_lam.shape[0])
    tiny = jnp.asarray(jnp.finfo(cur.dtype).tiny, cur.dtype)
    return (jnp.linalg.norm(cur - ref_lam)
            / jnp.maximum(jnp.linalg.norm(ref_lam), tiny))


def probe(state, hstate: HealthState, policy: HealthPolicy,
          ref_lam: Array | None = None) -> HealthState:
    """One in-graph health probe of a KPCAState-like (L, U, m) pytree.

    One M³ contraction + O(M²) reductions: every column is checked for
    orthogonality against the whole basis (which also catches
    row-support violations — an inactive row r carrying mass shows up in
    the r-th entry of UᵀU e_j), the active spectrum for negativity and
    non-finiteness.  Pure function of scalars-in /
    scalars-out: safe under jit, scan and vmap, no host sync.
    """
    L, U, m = state.L, state.U, state.m
    M = L.shape[0]
    dtype = L.dtype
    orth = orth_residual(U)
    act = rankone.active_mask(M, m)
    Lact = jnp.where(act, L, 0.0)
    lmax = jnp.max(jnp.abs(Lact))
    tiny = jnp.asarray(jnp.finfo(dtype).tiny, dtype)
    neg = jnp.maximum(-jnp.min(Lact), 0.0) / jnp.maximum(lmax, tiny)
    finite = jnp.all(jnp.isfinite(Lact)) & jnp.isfinite(orth)
    bad = (~finite).astype(jnp.int32)
    drift = (spectral_drift(state, ref_lam) if ref_lam is not None
             else hstate.spec_drift)
    return hstate._replace(
        orth_err=orth.astype(dtype), neg_frac=neg.astype(dtype),
        nonfinite=jnp.maximum(hstate.nonfinite, bad),
        probes=hstate.probes + 1,
        spec_drift=jnp.asarray(drift, dtype))


def verdict(hstate: HealthState, policy: HealthPolicy) -> Array:
    """Traced healthy/unhealthy boolean from the last probe."""
    return ((hstate.nonfinite == 0)
            & (hstate.orth_err <= policy.orth_tol)
            & (hstate.neg_frac <= policy.neg_tol))


def is_healthy(hstate: HealthState, policy: HealthPolicy) -> bool:
    """Host-side spelling of ``verdict`` (forces a sync — call once per
    block, not per point)."""
    return bool(verdict(hstate, policy))


@partial(jax.jit, static_argnames=("policy",))
def _probe_jit(state, hstate, policy):
    return probe(state, hstate, policy)


@partial(jax.jit, static_argnames=("policy",))
def _probe_ref_jit(state, hstate, policy, ref_lam):
    return probe(state, hstate, policy, ref_lam)


# -------------------------------------------------------- input gate --
def _gate(sub, x_new: Array, spec: kf.KernelSpec, policy: HealthPolicy
          ) -> tuple[Array, Array]:
    """Quarantine decision + sanitized stand-in for one offered point.

    Returns ``(ok, x_safe)``: ``ok`` is a traced boolean, ``x_safe`` is
    the point itself when accepted and the stored seed row ``X[0]`` when
    rejected — a well-conditioned stand-in (a real, finite point of the
    stream) so the unconditionally-executed update body cannot overflow,
    and its result is discarded by the caller's select anyway.
    """
    x_new = jnp.asarray(x_new, sub.X.dtype)
    if not policy.quarantine:
        return jnp.ones((), jnp.bool_), x_new
    ok = jnp.all(jnp.isfinite(x_new))
    stand_in = sub.X[0]
    if policy.outlier_tol > 0.0:
        x_tmp = jnp.where(ok, x_new, stand_in)
        a, k_new = eng.masked_row(sub, x_tmp, spec)
        amax = jnp.max(jnp.abs(a))
        ok = ok & ((amax >= policy.outlier_tol * k_new) | (sub.m == 0))
    return ok, jnp.where(ok, x_new, stand_in)


def _note_gate(hstate: HealthState, ok: Array) -> HealthState:
    rej = (~ok).astype(jnp.int32)
    return hstate._replace(quarantined=hstate.quarantined + rej,
                           rejected_last=rej)


def _select(ok, new, old):
    return jax.tree.map(lambda n, o: jnp.where(ok, n, o), new, old)


# ------------------------------------------------- guarded dispatches --
@partial(jax.jit, static_argnames=("spec", "adjusted", "plan", "Mb"))
def _guarded_update_impl(full, hstate, x_new, spec: kf.KernelSpec,
                         adjusted: bool, plan: eng.UpdatePlan, Mb: int):
    """slice → gate → ingest → scatter → full-level select → probe,
    all under ONE jit.  The final select runs at full capacity so a
    rejected point returns the caller's state bitwise even on bucketed
    dispatch (scatter would otherwise regenerate the sentinel tail)."""
    policy = plan.health
    M = full.L.shape[0]
    sub = eng.slice_state(full, Mb) if Mb < M else full
    ok, x_safe = _gate(sub, x_new, spec, policy)
    new = eng._ingest(sub, x_safe, spec, adjusted, plan.kernel_plan())
    out = eng.scatter_state(full, new) if Mb < M else new
    out = _select(ok, out, full)
    h = _note_gate(hstate, ok)
    h = probe(eng.slice_state(out, Mb) if Mb < M else out, h, policy)
    return out, h


@partial(jax.jit, static_argnames=("spec", "adjusted", "plan", "Mb"))
def _guarded_scan_chunk_impl(full, hstate, xs: Array, spec: kf.KernelSpec,
                             adjusted: bool, plan: eng.UpdatePlan, Mb: int):
    """Guarded mirror of ``engine._scan_chunk``: per-point gate+select
    inside the scan, ONE probe per chunk (the probe is for drift, which
    moves per-block, not per-point), full-level select when the whole
    chunk was rejected."""
    policy = plan.health
    kplan = plan.kernel_plan()
    M = full.L.shape[0]
    sub0 = eng.slice_state(full, Mb) if Mb < M else full

    def step(carry, x_new):
        st, h = carry
        ok, x_safe = _gate(st, x_new, spec, policy)
        new = eng._ingest(st, x_safe, spec, adjusted, kplan)
        return (_select(ok, new, st), _note_gate(h, ok)), ok

    (sub, h), oks = jax.lax.scan(step, (sub0, hstate), xs)
    out = eng.scatter_state(full, sub) if Mb < M else sub
    out = _select(jnp.any(oks), out, full)
    h = probe(sub, h, policy)
    return out, h


@partial(jax.jit, static_argnames=("spec", "adjusted", "plan", "Mb"))
def _guarded_grow_step_impl(kpca, ages: Array, clock: Array, hstate,
                            x_new: Array, spec: kf.KernelSpec,
                            adjusted: bool, plan: eng.UpdatePlan, Mb: int):
    """One guarded append-only window step: the arrival stamp and the
    clock advance only when the point is accepted, so quarantine leaves
    ring, ages and clock untouched (the ``window.ingest`` bugfix)."""
    policy = plan.health
    M = kpca.L.shape[0]
    sub = eng.slice_state(kpca, Mb) if Mb < M else kpca
    ok, x_safe = _gate(sub, x_new, spec, policy)
    new = eng._ingest(sub, x_safe, spec, adjusted, plan.kernel_plan())
    out = eng.scatter_state(kpca, new) if Mb < M else new
    out = _select(ok, out, kpca)
    ages_out = jnp.where(ok, ages.at[kpca.m].set(clock), ages)
    clock_out = jnp.where(ok, clock + 1, clock)
    h = _note_gate(hstate, ok)
    h = probe(eng.slice_state(out, Mb) if Mb < M else out, h, policy)
    return out, ages_out, clock_out, h


@partial(jax.jit, static_argnames=("spec", "adjusted", "plan", "Mb"))
def _guarded_window_chunk_impl(kpca, ages: Array, clock: Array, hstate,
                               xs: Array, spec: kf.KernelSpec,
                               adjusted: bool, plan: eng.UpdatePlan,
                               Mb: int):
    """Guarded mirror of ``engine._window_scan_chunk``: the evict+ingest
    pair executes unconditionally (fixed shapes, fixed collective
    schedule under shard_map) on the sanitized stand-in, and the select
    keeps state, ages AND clock untouched on rejection — so the ring
    stays consistent and a clean stream that never saw the bad point is
    indistinguishable.  Accepted count is recoverable on the host as
    ``clock_after − clock_before``."""
    from repro.core import downdate as dd

    policy = plan.health
    kplan = plan.kernel_plan()
    M = kpca.L.shape[0]
    sub0 = eng.slice_state(kpca, Mb) if Mb < M else kpca
    ages0 = ages[:Mb] if Mb < M else ages

    def step(carry, x_new):
        st, ag, ck, h = carry
        ok, x_safe = _gate(st, x_new, spec, policy)
        victim = jnp.argmin(ag).astype(jnp.int32)
        order = dd.boundary_perm(victim, st.m, ag.shape[0])
        st_n = eng._window_pair(st, victim, x_safe, spec, adjusted, kplan)
        ag_n = ag[order].at[st_n.m - 1].set(ck)
        return (_select(ok, st_n, st), jnp.where(ok, ag_n, ag),
                jnp.where(ok, ck + 1, ck), _note_gate(h, ok)), None

    (sub, ages_sub, clock_n, h), _ = jax.lax.scan(
        step, (sub0, ages0, clock, hstate), xs)
    if Mb < M:
        out = eng.scatter_state(kpca, sub)
        ages_out = ages.at[:Mb].set(ages_sub)
    else:
        out, ages_out = sub, ages_sub
    any_acc = clock_n > clock
    out = _select(any_acc, out, kpca)
    ages_out = jnp.where(any_acc, ages_out, ages)
    h = probe(sub, h, policy)
    return out, ages_out, clock_n, h


# --------------------------------------------------------- heal ladder --
def orth_residual(U: Array) -> Array:
    """Orthogonality residual max_j ‖(UᵀU − I) e_j‖₂ over all M columns
    (traced; non-finite when U holds a non-finite entry)."""
    E = (jnp.matmul(U.T, U, precision=MATMUL_PRECISION)
         - jnp.eye(U.shape[0], dtype=U.dtype))
    return jnp.sqrt(jnp.max(jnp.sum(E * E, axis=0)))


def exact_orth_residual(state) -> float:
    """Host-side spelling of ``orth_residual`` for a state; +inf when U
    holds non-finite entries."""
    r = float(orth_residual(state.U))
    return r if math.isfinite(r) else float("inf")


def polish(state):
    """Cheapest heal rung: QR re-orthonormalization of the eigenvector
    block, eigenvalues untouched.  Sign-fixed so Q stays aligned with U
    column-for-column.  Preserves the padding invariants exactly when
    the input does (active columns vanish on rows ≥ m ⇒ Gram–Schmidt
    never leaks mass into the inactive identity columns)."""
    Q, R = jnp.linalg.qr(state.U)
    s = jnp.sign(jnp.diagonal(R))
    s = jnp.where(s == 0, jnp.ones_like(s), s)
    return state._replace(U=Q * s[None, :])


def resync(state, spec: kf.KernelSpec, adjusted: bool):
    """Exact heal rung: re-diagonalize from the stored active points,
    mirroring ``inkpca.init_state`` — gram of X[:m], optional centering,
    eigh — and rebuild the S/K1 running sums.  Post-resync the state
    matches a batch KPCA of the same points by construction.  Raises
    ``HealthError`` (restore rung) when the stored points are corrupt.
    """
    from repro.core import inkpca

    m = int(state.m)
    M = state.L.shape[0]
    dtype = state.L.dtype
    Xa = state.X[:m]
    if not bool(jnp.all(jnp.isfinite(Xa))):
        raise HealthError(
            "stored points are non-finite — in-place resync impossible; "
            "restore from the last checkpoint")
    K0 = kf.gram_block(Xa, Xa, spec=spec)
    S = jnp.sum(K0)
    K1 = jnp.sum(K0, axis=1)
    Keff = kf.center_gram(K0) if adjusted else K0
    lam, vec = inkpca.gram_eigh(Keff)
    L = jnp.zeros((M,), dtype).at[:m].set(lam.astype(dtype))
    U = jnp.eye(M, dtype=dtype).at[:m, :m].set(vec.astype(dtype))
    L = rankone.sentinelize(L, state.m, jnp.zeros((), dtype))
    K1p = jnp.zeros((M,), dtype).at[:m].set(K1.astype(dtype))
    return state._replace(L=L, U=U, S=S.astype(dtype), K1=K1p)


def heal_kpca(state, spec: kf.KernelSpec, adjusted: bool,
              policy: HealthPolicy = DEFAULT_POLICY, *,
              level: str = "auto", rung_out: list | None = None):
    """Walk the escalation ladder on one KPCAState.

    ``level``: "polish" | "resync" force a rung; "auto" measures the
    exact residual and picks the cheapest rung that restores health —
    no-op when already healthy, polish for pure (small) orthogonality
    loss, resync when eigenvalues are implicated or the drift is past
    ``policy.polish_max``.  Non-finite stored points raise
    ``HealthError`` from every rung: that is the restore-from-checkpoint
    escalation, which only the caller (who owns the checkpoint
    directory) can execute.

    ``rung_out``: optional list; the rung actually taken ("noop" |
    "polish" | "resync") is appended — the telemetry layer's
    heals-by-rung counters read it without a second residual pass.
    """

    def took(rung: str):
        if rung_out is not None:
            rung_out.append(rung)

    m = int(state.m)
    if not bool(jnp.all(jnp.isfinite(state.X[:m]))):
        raise HealthError(
            "stored points are non-finite — restore from the last "
            "checkpoint")
    if level == "polish":
        took("polish")
        return polish(state)
    if level == "resync":
        took("resync")
        return resync(state, spec, adjusted)
    if level != "auto":
        raise ValueError(f"unknown heal level {level!r}")
    M = state.L.shape[0]
    Lact = jnp.where(rankone.active_mask(M, state.m), state.L, 0.0)
    lmax = float(jnp.max(jnp.abs(Lact)))
    eig_ok = (bool(jnp.all(jnp.isfinite(Lact)))
              and float(-jnp.min(Lact)) <= policy.neg_tol * max(lmax, 1e-30))
    r = exact_orth_residual(state)
    if eig_ok and r <= policy.orth_tol:
        took("noop")
        return state
    if eig_ok and r <= policy.polish_max:
        polished = polish(state)
        if exact_orth_residual(polished) <= policy.orth_tol:
            took("polish")
            return polished
    took("resync")
    return resync(state, spec, adjusted)
