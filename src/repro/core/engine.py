"""Unified update engine: one code path from kernel row to scatter.

Every consumer of the paper's rank-one eigendecomposition updates —
``inkpca.KPCAStream`` (Algorithms 1/2), ``nystrom.add_landmark`` (§4), the
row-sharded ``core/distributed.py`` drivers, and the ``serve.py`` streaming
service — used to re-thread its own ``method``/``matmul``/``iters``/
``dispatch`` kwargs, and only the first of them got bucketed dispatch and
the fused ±sigma pair.  This module centralizes that plumbing:

* ``UpdatePlan`` — a hashable (jit-static) description of *how* updates
  run: secular method, rotation backend, bisection iterations, bucket
  policy, fused-pair merge-fallback policy, shrink compaction.
* ``Engine`` — owns slice→update→scatter, bucket selection, and the
  fused-pair vs sequential choice for a single stream (KPCA or Nyström).
* ``StreamBatch`` — vmapped multi-tenant streaming: one stacked
  ``KPCAState`` advances B independent tenants per device step, bucketed
  at the cohort maximum active count.

Bucket geometry and invariants
------------------------------
The padding convention of ``rankone.py`` makes slicing sound:

* L is ascending with all inactive entries (sentinels) strictly *above*
  the active spectrum, so the m active eigenvalues always occupy
  ``L[:m]`` and ``L[:M_b]`` carries the active spectrum plus the lowest
  M_b − m sentinels — still ascending, still sentinels-on-top.
* Inactive columns of U are exact identity columns, and (U orthogonal)
  the active columns are zero on rows ≥ m.  Hence ``U[:M_b, :M_b]``
  loses nothing and the complement of the bucket is exactly I.
* K1 / X are zero beyond m; S is a scalar.

``slice_state`` therefore maps a capacity-M state with m < M_b active
pairs to a *valid* capacity-M_b state, and ``scatter_state`` writes the
updated bucket back (re-sentinelizing the tail of L).  The one exception
is a *truncated* state: ``Engine.truncate`` keeps eigenvector support on
the pre-truncation rows, so the engine buckets at the row-support bound
(``min_rows``) until ``compact`` re-expresses the system on the leading
rows — see those methods.

Retrace / bucket-crossing cost model
------------------------------------
Each jitted update specializes on the bucket capacity, so a stream pays
one compilation per bucket it visits — at most log2(M / min_bucket) + 1
of them, ever.  ``update_block`` additionally specializes the scan on the
chunk length; chunks are cut at bucket crossings, so a monotone stream
sees at most two shapes per bucket.  Bucket choice reads ``int(m)`` on
the host — one device sync per chunk (per point for ``update``), which
the scan amortizes.  ``UpdatePlan.kernel_plan()`` normalizes the fields
that do not affect numerics before they reach a jitted function, so
switching dispatch or bucket ladder never retraces the update kernels.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import kernels_fn as kf, rankone
from repro.core.precision import MATMUL_PRECISION

Array = jax.Array

DEFAULT_MIN_BUCKET = 128


class UpdatePlan(NamedTuple):
    """How updates run — hashable, so usable as a jit static argument.

    method:         secular-solve eigenvector variant ("gu" | "bns")
    matmul:         rotation backend — "jnp" | "pallas" (sequential ±sigma
                    updates) or "jnp2" | "pallas2" (fused double rotation)
    iters:          fixed bisection iteration count; None (default) resolves
                    per state dtype — 62 for f64, 32 for f32 (bracket widths
                    shrink 2^-iters relative, so 32 is still ~500x beyond
                    f32 resolution; see ``resolve_iters``)
    dispatch:       "fixed" (capacity-M every step) | "bucketed"
    min_bucket:     smallest rung of the power-of-two bucket ladder
    merge_fallback: cond-guard the fused pair back to the sequential path
                    when a dlaed2 cluster-merge fires (safe on clustered
                    spectra; the O(M³) rotation is what's conditional).
                    Note: under vmap (StreamBatch) lax.cond lowers to a
                    select that executes BOTH branches — fused multi-tenant
                    plans should set merge_fallback=False or use the
                    sequential matmul spellings
    compact_shrink: default for Engine.truncate(compact=...) — re-express
                    a truncated state on its leading rows and shrink the
                    arrays to the active bucket
    precise:        solve the secular systems in f64 when x64 is enabled
    window:         default sliding-window size for streams built from
                    this plan (``KPCAStream``/``StreamBatch`` evict the
                    oldest point before ingesting past the window); None
                    keeps the append-only behaviour
    landmark_policy: Nyström landmark admission — "append" (every offered
                    point becomes a landmark, the paper's §4 loop) or
                    "leverage" (admit on projection residual, replace the
                    lowest-leverage landmark when at budget; see
                    ``nystrom.consider_landmark``)
    fuse_krow:      produce each ingest's kernel row fused with its
                    eigenbasis projection (``kernels/rbf_gram.krow_project``)
                    instead of a standalone gram dispatch followed by the
                    update's own Uᵀv pass — one read of U for the whole
                    prologue.  Changes the traced graph (NOT normalized by
                    ``kernel_plan``); numerics agree with the unfused
                    reference to rotation tolerance.
    serve_every:    decoupled-serving policy: publish a fresh
                    ``core/serving.ServingSnapshot`` every N ingest blocks
                    (``launch/serve.IngestServeLoop``); queries batch
                    against the last published snapshot in between
    serve_components: projection width C frozen into published snapshots
                    (the S matrix is (M, C)); queries return C components
    health:         a ``core/health.HealthPolicy`` (hashable NamedTuple,
                    jit-static like the rest of the plan) enabling the
                    self-healing layer: in-graph probes + input
                    quarantine on the ``*_guarded`` dispatches, heal
                    thresholds for ``Engine.heal``/``KPCAStream``, and
                    the drift threshold for staleness-aware publication
                    (``launch/serve.IngestServeLoop``).  None (default)
                    keeps every pre-existing path bit-identical;
                    normalized away by ``kernel_plan`` so the inner
                    update kernels never re-specialize per policy.
    metrics:        enable the in-graph telemetry lane
                    (``core/telemetry.MetricsState`` riding the stream in
                    ``KPCAStream``/``StreamBatch``).  Metric notes NEVER
                    enter the update dispatches — the eigensystem goes
                    through the identical jitted callables either way, so
                    metrics-on state is bitwise metrics-off state (see
                    ``core/telemetry.py``); normalized away by
                    ``kernel_plan`` accordingly.
    """

    method: str = "gu"
    matmul: str = "jnp"
    iters: int | None = None
    dispatch: str = "fixed"
    min_bucket: int = DEFAULT_MIN_BUCKET
    merge_fallback: bool = True
    compact_shrink: bool = False
    precise: bool = True
    window: int | None = None
    landmark_policy: str = "append"
    fuse_krow: bool = False
    serve_every: int = 1
    serve_components: int = 8
    health: object | None = None
    metrics: bool = False

    @property
    def fused(self) -> bool:
        return self.matmul in ("jnp2", "pallas2")

    @property
    def inner_matmul(self) -> str:
        """The single-rotation backend behind a possibly-fused spelling."""
        return {"jnp2": "jnp", "pallas2": "pallas"}.get(self.matmul,
                                                        self.matmul)

    def kernel_plan(self) -> "UpdatePlan":
        """Normalize fields that do not change update numerics, so jitted
        updates are cached once per (method, matmul, iters, ...) rather
        than once per dispatch/bucket-ladder combination."""
        return self._replace(dispatch="fixed",
                             min_bucket=DEFAULT_MIN_BUCKET,
                             compact_shrink=False,
                             window=None,
                             landmark_policy="append",
                             serve_every=1,
                             serve_components=8,
                             health=None,
                             metrics=False)


DEFAULT_PLAN = UpdatePlan()


def resolve_iters(iters: int | None, dtype) -> int:
    """Bisection iteration count for a plan: explicit value, or the dtype
    default (the bracket width shrinks 2^-iters relative per root, so f32
    needs far fewer passes than the f64-calibrated 62)."""
    if iters is not None:
        return iters
    return 62 if jnp.dtype(dtype).itemsize >= 8 else 32


# ------------------------------------------------------- bucket geometry --
def bucket_sizes(capacity: int, min_bucket: int = DEFAULT_MIN_BUCKET
                 ) -> tuple[int, ...]:
    """Power-of-two ladder min_bucket, 2·min_bucket, …, capped at capacity.

    The capacity itself is always the top rung (even when not a power of
    two) so every state the fixed-capacity API accepts is representable.
    """
    if capacity <= 0:
        raise ValueError(f"capacity must be positive, got {capacity}")
    sizes = []
    b = min(min_bucket, capacity)
    while b < capacity:
        sizes.append(b)
        b *= 2
    sizes.append(capacity)
    return tuple(sizes)


def bucket_for(m_needed: int, capacity: int,
               min_bucket: int = DEFAULT_MIN_BUCKET) -> int:
    """Smallest bucket that can hold ``m_needed`` active pairs."""
    if m_needed > capacity:
        raise ValueError(
            f"need room for {m_needed} active pairs but capacity is "
            f"{capacity} — grow the state before streaming more points")
    for b in bucket_sizes(capacity, min_bucket):
        if b >= m_needed:
            return b
    raise AssertionError("unreachable: capacity is always a bucket")


# ------------------------------------------------------- slice / scatter --
def slice_state(state, Mb: int):
    """View the leading M_b×M_b block as a capacity-M_b state (see module
    docstring for why this is lossless while m < M_b)."""
    return state._replace(L=state.L[:Mb], U=state.U[:Mb, :Mb],
                          K1=state.K1[:Mb], X=state.X[:Mb])


def scatter_state(full, sub):
    """Write an updated bucket back into the fixed-capacity state."""
    Mb = sub.L.shape[0]
    L = full.L.at[:Mb].set(sub.L)
    # The tail L[Mb:] still holds sentinels for the *pre-update* spectrum;
    # regenerate so the whole array is ascending with sentinels on top.
    L = rankone.sentinelize(L, sub.m, jnp.zeros((), L.dtype))
    return full._replace(L=L, U=full.U.at[:Mb, :Mb].set(sub.U), m=sub.m,
                         S=sub.S, K1=full.K1.at[:Mb].set(sub.K1),
                         X=full.X.at[:Mb].set(sub.X))


def _slice_stacked(states, Mb: int):
    """Leading-axis (tenant-batched) version of ``slice_state``."""
    return states._replace(L=states.L[:, :Mb], U=states.U[:, :Mb, :Mb],
                           K1=states.K1[:, :Mb], X=states.X[:, :Mb])


def _scatter_stacked(full, sub):
    return jax.vmap(scatter_state)(full, sub)


# ------------------------------------------------------ shared primitives --
@jax.named_scope("kpca.krow")
def masked_row(state, x_new: Array, spec: kf.KernelSpec
               ) -> tuple[Array, Array]:
    """Kernel row against stored points, zeroed beyond the active count."""
    a_full = kf.kernel_row(x_new, state.X, spec=spec)
    mask = rankone.active_mask(state.X.shape[0], state.m)
    a = jnp.where(mask, a_full, 0.0)
    k_new = kf.gram_block(x_new[None], x_new[None], spec=spec)[0, 0]
    return a, k_new


def apply_pair(L: Array, U: Array, v1: Array, sigma1: Array, v2: Array,
               sigma2: Array, m: Array, *, plan: UpdatePlan,
               z1: Array | None = None, z2: Array | None = None
               ) -> tuple[Array, Array]:
    """Apply a ±sigma update pair under ``plan``: one fused double rotation
    (matmul 'jnp2'/'pallas2'; cond-guarded back to sequential when a
    cluster-merge fires and plan.merge_fallback is set) or two sequential
    rank-one updates.

    ``z1``/``z2`` are optional precomputed Uᵀv₁/Uᵀv₂ in the CURRENT basis
    (from the fused ingest kernel).  The fused pair consumes both; the
    sequential spelling can only reuse z1 — z2 is stale after the first
    rotation, so the second update recomputes its own projection."""
    iters = resolve_iters(plan.iters, L.dtype)
    if plan.fused:
        return rankone.rank_one_update_pair(
            L, U, v1, sigma1, v2, sigma2, m, method=plan.method,
            matmul=plan.inner_matmul, iters=iters, precise=plan.precise,
            merge_fallback=plan.merge_fallback, z1=z1, z2=z2)
    L, U = rankone.rank_one_update(L, U, v1, sigma1, m, method=plan.method,
                                   matmul=plan.matmul, iters=iters,
                                   precise=plan.precise, z=z1)
    return rankone.rank_one_update(L, U, v2, sigma2, m, method=plan.method,
                                   matmul=plan.matmul, iters=iters,
                                   precise=plan.precise)


def rank_one(L: Array, U: Array, v: Array, sigma: Array, m: Array, *,
             plan: UpdatePlan) -> tuple[Array, Array]:
    """One ``rankone.rank_one_update`` under ``plan``: run at the active
    bucket and scatter back (no kernel involved — usable without an
    Engine)."""
    M = L.shape[0]
    Mb = (M if plan.dispatch != "bucketed"
          else bucket_for(max(int(m), 1), M, plan.min_bucket))
    kwargs = dict(method=plan.method, matmul=plan.inner_matmul,
                  iters=resolve_iters(plan.iters, L.dtype),
                  precise=plan.precise)
    if Mb == M:
        return rankone.rank_one_update(L, U, v, sigma, m, **kwargs)
    Lb, Ub = rankone.rank_one_update(L[:Mb], U[:Mb, :Mb], v[:Mb], sigma, m,
                                     **kwargs)
    L_new = rankone.sentinelize(L.at[:Mb].set(Lb), m, jnp.zeros((), L.dtype))
    return L_new, U.at[:Mb, :Mb].set(Ub)


def eigpairs(state) -> tuple[Array, Array]:
    """Active (descending) eigenvalues and eigenvectors."""
    M = state.L.shape[0]
    mask = rankone.active_mask(M, state.m)
    order = jnp.argsort(jnp.where(mask, -state.L, jnp.inf))
    return state.L[order], state.U[:, order]


def transform_state(state, x: Array, *, spec: kf.KernelSpec, adjusted: bool,
                    n_components: int, plan: UpdatePlan | None = None
                    ) -> Array:
    """Project points on the leading kernel principal components (pure
    function of the state — vmappable across tenants).

    With ``plan.fuse_krow`` the query gram is never materialized: the
    fused ``nystrom_recon.transform_project`` kernel produces each K_q
    tile in VMEM and contracts it against S = U_active / sqrt(lam) in the
    same pass, returning (Y, rowsum).  The mean-adjusted centering is
    then an affine post-correction of Y: with colsum = 1ᵀS (S rows >= m
    vanish — active columns live on the active prefix) and
    colproj = (K1/m) @ S,

        Y_adj = Y − (rowsum/m)·colsumᵀ − 1·colprojᵀ + (S_sum/m²)·colsumᵀ

    which equals centering the masked gram before projecting.

    Implemented as publish-then-query over ``core/serving``: an ephemeral
    ``ServingSnapshot`` is built (the eigpair sort / top-C gather /
    rescale prologue) and the shared query head projects against it — so
    a transform of a frozen state is bit-identical to serving queries
    against a snapshot published from that state, by construction.  The
    decoupled-serving path hoists the publish out of the per-query cost
    entirely (``serving.DoubleBuffer`` keeps it off the query path)."""
    from repro.core import serving
    snap = serving.publish_transform(state, n_components=n_components,
                                     adjusted=adjusted)
    return serving.query(snap, x, spec=spec, plan=plan)


# ------------------------------------------------------- jitted update fns --
def _ingest(st, x_new: Array, spec: kf.KernelSpec, adjusted: bool,
            plan: UpdatePlan):
    """One Algorithm-1/2 ingest under ``plan`` — THE shared prologue of
    every consumer (stream, scan, window, multi-tenant, Nyström).

    ``plan.fuse_krow`` routes through ``inkpca.ingest_*``: the kernel row
    is produced tile-by-tile fused with its eigenbasis projection
    (``kernels/rbf_gram.krow_project``), so U is read once for the whole
    prologue.  Otherwise the reference two-dispatch path runs: standalone
    masked kernel row, then the update's own Uᵀv pass."""
    from repro.core import inkpca
    if plan.fuse_krow:
        fn = inkpca.ingest_adjusted if adjusted else inkpca.ingest_unadjusted
        return fn(st, x_new, spec=spec, plan=plan)
    a, k_new = masked_row(st, x_new, spec)
    fn = inkpca.update_adjusted if adjusted else inkpca.update_unadjusted
    return fn(st, a, k_new, x_new, plan=plan)


def _window_pair(st, victim, x_new: Array, spec: kf.KernelSpec,
                 adjusted: bool, plan: UpdatePlan):
    """The steady-state ``evict|ingest`` pair stage at m ≡ W: inverse
    ±sigma pair + contraction on the victim row, then one Algorithm-1/2
    ingest.  THE shared windowed composition — the single-stream scan,
    the guarded scan (``health._guarded_window_chunk_impl``) and the
    multi-tenant lockstep scan all fold this exact pair; the sharded
    mirror (``distributed._window_step_sharded``) composes the same two
    stages from the sharded bodies."""
    from repro.core import downdate as dd

    st = dd.downdate(st, victim, spec, adjusted=adjusted, plan=plan)
    return _ingest(st, x_new, spec, adjusted, plan)


@partial(jax.jit, static_argnames=("spec", "adjusted", "plan"))
def _scan_chunk(sub, xs: Array, spec: kf.KernelSpec, adjusted: bool,
                plan: UpdatePlan):
    """Fixed-capacity scan over a chunk that fits inside one bucket."""
    def step(st, x_new):
        return _ingest(st, x_new, spec, adjusted, plan), None

    out, _ = jax.lax.scan(step, sub, xs)
    return out


@partial(jax.jit, static_argnames=("spec", "adjusted", "plan"))
def _batched_update(states, xs: Array, spec: kf.KernelSpec,
                    adjusted: bool, plan: UpdatePlan):
    """One vmapped step: fold xs[i] into tenant i, all tenants active."""
    def one(st, x):
        return _ingest(st, x, spec, adjusted, plan)

    return jax.vmap(one)(states, xs)


@partial(jax.jit, static_argnames=("spec", "adjusted", "plan"))
def _batched_update_masked(states, xs: Array, active: Array,
                           spec: kf.KernelSpec, adjusted: bool,
                           plan: UpdatePlan):
    """One vmapped step: fold xs[i] into tenant i where active[i]."""
    def one(st, x, act):
        new = _ingest(st, x, spec, adjusted, plan)
        return jax.tree.map(lambda n, o: jnp.where(act, n, o), new, st)

    return jax.vmap(one)(states, xs, active)


@partial(jax.jit, static_argnames=("spec", "adjusted", "plan"))
def _batched_downdate_masked(states, rows: Array, active: Array,
                             spec: kf.KernelSpec, adjusted: bool,
                             plan: UpdatePlan):
    """One vmapped step: evict row rows[i] from tenant i where active[i]
    (the decremental mirror of ``_batched_update_masked``)."""
    from repro.core import downdate as dd

    def one(st, r, act):
        new = dd.downdate(st, r, spec, adjusted=adjusted, plan=plan)
        return jax.tree.map(lambda n, o: jnp.where(act, n, o), new, st)

    return jax.vmap(one)(states, rows, active)


@partial(jax.jit, static_argnames=("spec", "adjusted", "plan"))
def _batched_scan_masked(states, xs: Array, active: Array,
                         spec: kf.KernelSpec, adjusted: bool,
                         plan: UpdatePlan):
    """Scan a (T, B, d) block with a T-constant tenant mask (used by
    padded cohorts, whose pad lanes must never advance)."""
    def step(sts, x_row):
        def one(st, x, act):
            new = _ingest(st, x, spec, adjusted, plan)
            return jax.tree.map(lambda n, o: jnp.where(act, n, o), new, st)

        return jax.vmap(one)(sts, x_row, active), None

    out, _ = jax.lax.scan(step, states, xs)
    return out


@partial(jax.jit, static_argnames=("spec", "adjusted", "plan"))
def _window_scan_chunk(sub, ages: Array, clock: Array, xs: Array,
                       spec: kf.KernelSpec, adjusted: bool,
                       plan: UpdatePlan):
    """Steady-state sliding-window scan: every step evicts the oldest
    point and ingests one new one, all under ONE dispatch.

    At m ≡ W the evict+ingest pair is a fixed-shape composition (inverse
    ±sigma pair + Householder contraction at m = W, then the forward
    update back to W), so a whole (T, d) block folds through a single
    ``lax.scan`` — the windowed mirror of ``_scan_chunk``.  The arrival
    ring advances fully in-graph: the victim is ``argmin(ages)`` (a
    traced read, not the host-side ``oldest_row``), the survivor
    permutation reuses ``downdate.boundary_perm``, and the new point is
    stamped with the traced clock.  Zero host syncs inside the block;
    the caller hoists the rebase check to once per block.
    """
    from repro.core import downdate as dd

    def step(carry, x_new):
        st, ages, clock = carry
        victim = jnp.argmin(ages).astype(jnp.int32)
        order = dd.boundary_perm(victim, st.m, ages.shape[0])
        # No sentinel write for the evicted slot: at m ≡ W the freed
        # boundary row W−1 is exactly where the new point lands.
        st = _window_pair(st, victim, x_new, spec, adjusted, plan)
        ages = ages[order].at[st.m - 1].set(clock)     # new point's row
        return (st, ages, clock + 1), None

    (sub, ages, clock), _ = jax.lax.scan(step, (sub, ages, clock), xs)
    return sub, ages, clock


@partial(jax.jit, static_argnames=("spec", "adjusted", "plan"))
def _batched_window_scan_masked(states, xs: Array, active: Array,
                                spec: kf.KernelSpec, adjusted: bool,
                                plan: UpdatePlan):
    """Scan a (T, B, d) block of steady-state window steps: every active
    tenant sits at m ≡ W, evicts its oldest point (physical row 0 —
    lockstep FIFO, see ``StreamBatch``) and ingests, one device dispatch
    for the whole block.  ``active`` is T-constant (pad lanes and parked
    tenants stay bitwise untouched), which is what makes the whole block
    a fixed-shape scan — the windowed mirror of ``_batched_scan_masked``.
    """
    def step(sts, x_row):
        def one(st, x, act):
            new = _window_pair(st, jnp.zeros((), jnp.int32), x, spec,
                               adjusted, plan)
            return jax.tree.map(lambda n, o: jnp.where(act, n, o), new, st)

        return jax.vmap(one)(sts, x_row, active), None

    out, _ = jax.lax.scan(step, states, xs)
    return out


@partial(jax.jit, static_argnames=("spec", "adjusted", "plan"))
def _batched_scan(states, xs: Array, spec: kf.KernelSpec, adjusted: bool,
                  plan: UpdatePlan):
    """Scan a (T, B, d) block: T sequential steps, B tenants per step."""
    def step(sts, x_row):
        def one(st, x):
            return _ingest(st, x, spec, adjusted, plan)

        return jax.vmap(one)(sts, x_row), None

    out, _ = jax.lax.scan(step, states, xs)
    return out


# ------------------------------------------------------- stream bundle --
class StreamState(NamedTuple):
    """The unified stream bundle the composed pipeline advances.

    One pytree carries everything a stream can accumulate: the
    eigensystem plus the OPTIONAL cross-cutting members — the sliding
    window's arrival ring, the self-healing layer's ``HealthState``, the
    telemetry lane's ``MetricsState``.  Absent members are ``None``
    leaves (``None`` is an empty pytree node), so the treestructure is a
    pure function of the plan: jit never retraces because a member
    appeared mid-stream, and ``Engine.step``/``step_block`` select their
    stages from the bundle SHAPE at trace time —

        gate  — runs iff ``health``  is present (quarantine + probe)
        evict — runs iff ``ages``    is present (sliding-window FIFO)
        note  — runs iff ``metrics`` is present (telemetry accounting)

    ``kpca``    ``inkpca.KPCAState`` — the fixed-capacity eigensystem
    ``ages``    (M,) arrival ring, or None for append-only streams
    ``clock``   () next arrival stamp (present iff ``ages`` is)
    ``health``  ``health.HealthState`` or None
    ``metrics`` ``telemetry.MetricsState`` or None
    """

    kpca: object
    ages: object = None
    clock: object = None
    health: object = None
    metrics: object = None

    @property
    def windowed(self) -> bool:
        return self.ages is not None


def make_stream(state, *, health=None, metrics=None) -> StreamState:
    """Wrap a bare ``KPCAState`` or a ``window.WindowState`` (plus any
    riding layers) into the bundle ``Engine.step`` advances.  The inverse
    is structural: read ``.kpca`` (or rebuild a ``WindowState`` from
    ``kpca``/``ages``/``clock``), ``.health``, ``.metrics``."""
    if hasattr(state, "kpca"):                         # WindowState
        return StreamState(kpca=state.kpca, ages=state.ages,
                           clock=state.clock, health=health, metrics=metrics)
    return StreamState(kpca=state, health=health, metrics=metrics)


# ---------------------------------------------------------------- engine --
class Engine:
    """Slice→update→scatter for one stream, under an ``UpdatePlan``.

    The engine is stateless with respect to the stream (states go in and
    out), so one engine can serve many states with the same plan/kernel.
    Streams advance through the composed ``step``/``step_block``
    pipeline; the pre-collapse cartesian spellings survive as one-line
    deprecation shims (see the marked block below).
    """

    def __init__(self, spec: kf.KernelSpec, plan: UpdatePlan = DEFAULT_PLAN,
                 *, adjusted: bool = True):
        self.spec = spec
        self.plan = plan
        self.adjusted = adjusted

    # ---- bucket selection -------------------------------------------------
    def _bucket(self, capacity: int, need: int) -> int:
        if self.plan.dispatch != "bucketed":
            return capacity
        return bucket_for(need, capacity, self.plan.min_bucket)

    # ---- KPCA streaming ---------------------------------------------------
    def _kpca_step(self, state, x_new):
        return _ingest(state, x_new, self.spec, self.adjusted,
                       self.plan.kernel_plan())

    # ---- composed stream-step pipeline -------------------------------------
    # THE update path.  ``step``/``step_block`` advance a ``StreamState``
    # bundle through up to four stages, selected at TRACE TIME from the
    # bundle's structure (absent members are None leaves):
    #
    #     gate → (evict|ingest|pair) → note
    #
    #     gate          health present:  quarantine gate + in-graph probe
    #                   (the guarded impls in ``core/health.py``)
    #     evict         ages present:    FIFO eviction, fused with the
    #                   ingest at m ≡ W (``_window_pair``)
    #     ingest|pair   always:          Algorithm 1/2 expansion + ±sigma
    #                   pair (``_ingest``)
    #     note          metrics present: one tiny separate accounting
    #                   dispatch (``telemetry.note_block``)
    #
    # Every stage routes through the SAME jitted impls the pre-collapse
    # variant methods used (``_scan_chunk``, ``_window_scan_chunk``,
    # ``health._guarded_*_impl``, ``telemetry.note_block``), so each of
    # the 2×2×2 (window × health × metrics) combinations is bitwise
    # identical to its legacy spelling — and a future cross-cutting
    # feature is ONE new stage here, not 2^k new methods.

    def _stream_window(self, stream: "StreamState",
                       window: int | None) -> int | None:
        if window is None:
            window = self.plan.window
        if stream.ages is not None and window is None:
            raise ValueError(
                "windowed StreamState needs a window size — pass window= "
                "or build the engine with UpdatePlan(window=W)")
        return window if stream.ages is not None else None

    def step(self, stream: "StreamState", x_new: Array, *,
             window: int | None = None, min_rows: int = 0) -> "StreamState":
        """Advance the bundle by ONE offered point through the composed
        gate → (evict|ingest|pair) → note pipeline.  Absent members stay
        absent; ``window`` defaults to the plan's and is required only
        for windowed bundles.  Point-wise windowed steps keep the
        two-dispatch evict+ingest spelling (the evict decision reads
        ``int(m)`` on the host); fold blocks through ``step_block`` for
        the single-dispatch steady-state scan."""
        from repro.core import window as wnd

        window = self._stream_window(stream, window)
        metered = stream.metrics is not None
        if metered:
            m0, c0 = stream.kpca.m, stream.clock
            q0 = (stream.health.quarantined if stream.health is not None
                  else None)
        if stream.ages is not None:
            w = wnd.WindowState(kpca=stream.kpca, ages=stream.ages,
                                clock=stream.clock)
            if stream.health is not None:
                w, h = self._gated_window_point(w, stream.health, x_new,
                                                window=window,
                                                min_rows=min_rows)
                stream = stream._replace(kpca=w.kpca, ages=w.ages,
                                         clock=w.clock, health=h)
            else:
                w = self._window_point(w, x_new, window=window,
                                       min_rows=min_rows)
                stream = stream._replace(kpca=w.kpca, ages=w.ages,
                                         clock=w.clock)
        elif stream.health is not None:
            st, h = self._gated_point(stream.kpca, stream.health, x_new,
                                      min_rows=min_rows)
            stream = stream._replace(kpca=st, health=h)
        else:
            stream = stream._replace(kpca=self._ingest_point(
                stream.kpca, x_new, min_rows=min_rows))
        if metered:
            stream = self._note_stage(stream, m0, c0, q0, offered=1,
                                      window=window)
        return stream

    def step_block(self, stream: "StreamState", xs: Array, *,
                   window: int | None = None,
                   min_rows: int = 0) -> "StreamState":
        """Fold a (T, d) block through the composed pipeline — the block
        mirror of ``step``.  Windowed bundles scan steady-state points
        under ONE dispatch (victim selection and the arrival ring fully
        in-graph); guarded bundles gate per point inside the scan; the
        note stage accounts the whole block once at the end."""
        from repro.core import window as wnd

        xs = jnp.asarray(xs)
        window = self._stream_window(stream, window)
        metered = stream.metrics is not None
        if metered:
            m0, c0 = stream.kpca.m, stream.clock
            q0 = (stream.health.quarantined if stream.health is not None
                  else None)
        if stream.ages is not None:
            w = wnd.WindowState(kpca=stream.kpca, ages=stream.ages,
                                clock=stream.clock)
            if stream.health is not None:
                w, h = self._gated_window_block(w, stream.health, xs,
                                                window=window,
                                                min_rows=min_rows)
                stream = stream._replace(kpca=w.kpca, ages=w.ages,
                                         clock=w.clock, health=h)
            else:
                w = self._window_block(w, xs, window=window,
                                       min_rows=min_rows)
                stream = stream._replace(kpca=w.kpca, ages=w.ages,
                                         clock=w.clock)
        elif stream.health is not None:
            st, h = self._gated_block(stream.kpca, stream.health, xs,
                                      min_rows=min_rows)
            stream = stream._replace(kpca=st, health=h)
        else:
            stream = stream._replace(kpca=self._ingest_block(
                stream.kpca, xs, min_rows=min_rows))
        if metered:
            stream = self._note_stage(stream, m0, c0, q0,
                                      offered=xs.shape[0], window=window)
        return stream

    def _note_stage(self, stream: "StreamState", m0, c0, q0, *,
                    offered: int, window: int | None) -> "StreamState":
        """The note stage: account the step into the riding MetricsState
        as ONE tiny separate dispatch, leaving the eigensystem path's jit
        cache entries untouched.  Accepted-count identities (all traced,
        zero host syncs): windowed bundles use the clock delta (guarded
        scans advance the clock only for accepted points); guarded plain
        bundles use the quarantine-counter delta; unguarded plain bundles
        accept everything offered."""
        from repro.core import telemetry as tm

        if c0 is not None:
            accepted = stream.clock - c0
        elif q0 is not None:
            accepted = offered - (stream.health.quarantined - q0)
        else:
            accepted = offered
        return stream._replace(metrics=tm.note_block(
            stream.metrics, m0, stream.kpca.m, offered, accepted,
            stream.health, window=window))

    # ---- stage impls: plain ingest -----------------------------------------
    def _ingest_point(self, state, x_new: Array, *, min_rows: int = 0):
        """One streaming point through Algorithm 1/2 at bucket capacity.

        The kernel row is evaluated against the sliced X as well, so the
        whole step — gram row, secular solve, rotation — is O(M_b²)/O(M_b³).
        ``min_rows`` is a row-support floor (a truncated, uncompacted state
        keeps eigenvector mass on rows beyond m — see ``truncate``).
        """
        M = state.L.shape[0]
        Mb = self._bucket(M, max(int(state.m) + 1, min_rows))
        sub = slice_state(state, Mb) if Mb < M else state
        sub = self._kpca_step(sub, x_new)
        return scatter_state(state, sub) if Mb < M else sub

    def _ingest_block(self, state, xs: Array, *, min_rows: int = 0):
        """Stream a block of points: scan within a bucket, re-bucket at
        crossings (see the cost model in the module docstring)."""
        M = state.L.shape[0]
        n = xs.shape[0]
        plan = self.plan.kernel_plan()
        i = 0
        while i < n:
            m = int(state.m)
            Mb = self._bucket(M, max(m + 1, min_rows))
            # Bucketed dispatch cuts chunks at crossings — including at the
            # top bucket, so exhaustion raises (via bucket_for) instead of
            # silently clamping writes past capacity.  Fixed dispatch keeps
            # the legacy one-scan semantics.
            take = (min(Mb - m, n - i) if self.plan.dispatch == "bucketed"
                    else n - i)
            sub = slice_state(state, Mb) if Mb < M else state
            sub = _scan_chunk(sub, xs[i:i + take], self.spec, self.adjusted,
                              plan)
            state = scatter_state(state, sub) if Mb < M else sub
            i += take
        return state

    # ---- decremental path --------------------------------------------------
    def downdate(self, state, i: int, *, min_rows: int = 0):
        """Remove point ``i`` from the stream at bucket capacity — the
        decremental mirror of ``update`` (see ``core/downdate.py``).

        The downdate never grows the system, so the bucket only needs to
        hold the CURRENT active count; once m drops below a rung, the
        next call (update or downdate) re-buckets downward automatically
        since bucket choice reads ``int(m)``.  A ``NystromState`` routes
        to ``remove_landmark``.  Requires m ≥ 2.
        """
        if hasattr(state, "kpca"):
            return self.remove_landmark(state, i, min_rows=min_rows)
        from repro.core import downdate as dd

        M = state.L.shape[0]
        m = int(state.m)
        if m < 2:
            raise ValueError(f"downdate needs at least 2 active points, "
                             f"got m={m}")
        if not 0 <= i < m:
            raise ValueError(f"point index {i} outside active range "
                             f"[0, {m})")
        Mb = self._bucket(M, max(m, min_rows, 1))
        sub = slice_state(state, Mb) if Mb < M else state
        sub = dd.downdate(sub, jnp.asarray(i, jnp.int32), self.spec,
                          adjusted=self.adjusted,
                          plan=self.plan.kernel_plan())
        return scatter_state(state, sub) if Mb < M else sub

    def replace(self, state, i: int, x_new: Array, *, min_rows: int = 0):
        """Swap point ``i`` for ``x_new``: downdate then update, both at
        bucket capacity.  Works on full states (downdate first frees the
        slot the update needs).  A ``NystromState`` routes to
        ``replace_landmark`` (grow_rows mode)."""
        if hasattr(state, "kpca"):
            return self.replace_landmark(state, None, i, x_new,
                                         min_rows=min_rows)
        state = self.downdate(state, i, min_rows=min_rows)
        return self.update(state, x_new, min_rows=min_rows)

    # ---- steady-state sliding window ---------------------------------------
    def _window_bucket(self, M: int, window: int, min_rows: int) -> int:
        """Bucket for a steady-state window step: the downdate runs at
        m = W and the following update needs W rows (m = W−1 growing by
        one), so the whole evict+ingest pair fits at bucket_for(W)."""
        return self._bucket(M, max(window, min_rows, 1))

    # ---- stage impls: window (evict|ingest fused) ---------------------------
    def _window_point(self, wstate, x_new: Array, *, window: int,
                      min_rows: int = 0):
        """Point-wise evict|ingest: append-only below a full window,
        evict-oldest + ingest at m ≡ W — the two-dispatch spelling
        ``window.ingest`` established (the evict decision reads
        ``int(m)`` on the host, the same sync bucket selection pays).
        Blocks fold through ``_window_block``'s single-dispatch scan."""
        from repro.core import window as wnd

        wstate = wnd.maybe_rebase(wstate)
        if int(wstate.kpca.m) >= window:
            wstate = wnd.evict(self, wstate, wnd.oldest_row(wstate),
                               min_rows=min_rows)
        kpca = self._ingest_point(wstate.kpca, jnp.asarray(x_new),
                                  min_rows=min_rows)
        ages = wstate.ages.at[wstate.kpca.m].set(wstate.clock)
        return wnd.WindowState(kpca=kpca, ages=ages,
                               clock=wstate.clock + 1)

    def _window_block(self, wstate, xs: Array, *, window: int,
                      min_rows: int = 0):
        """Fold a (T, d) block into a windowed stream — the windowed
        mirror of ``_ingest_block``.

        Growth phase (m < W): the leading W − m points are append-only
        and route through ``_ingest_block`` (scan within buckets), with
        their arrival stamps written in one fused slice.  Steady state
        (m ≡ W): the remaining points fold through ``_window_scan_chunk``
        — ONE dispatch for the whole chunk, victim selection and the
        arrival ring fully in-graph, zero host syncs inside the block.
        The rebase check is hoisted to once per block (the clock advances
        by exactly T), so no per-point ``int(clock)`` read either.
        """
        from repro.core import window as wnd

        xs = jnp.asarray(xs)
        T = xs.shape[0]
        if T == 0:
            return wstate
        m = int(wstate.kpca.m)
        if m > window:
            raise ValueError(f"active count {m} exceeds window {window}")
        # Hoisted rebase guard: one host clock read per block.
        if int(wstate.clock) + T >= wnd.age_sentinel(wstate.ages.dtype) - 1:
            wstate = wnd.rebase_ages(wstate)
        i = 0
        if m < window:
            g = min(window - m, T)
            grown = self._ingest_block(wstate.kpca, xs[:g],
                                       min_rows=min_rows)
            wstate = wnd.stamp_grown_ages(wstate, grown, g)
            i = g
        if i == T:
            return wstate
        M = wstate.kpca.L.shape[0]
        Mb = self._window_bucket(M, window, min_rows)
        plan = self.plan.kernel_plan()
        sub = slice_state(wstate.kpca, Mb) if Mb < M else wstate.kpca
        ages_sub = wstate.ages[:Mb] if Mb < M else wstate.ages
        sub, ages_sub, clock = _window_scan_chunk(
            sub, ages_sub, wstate.clock, xs[i:], self.spec, self.adjusted,
            plan)
        if Mb < M:
            kpca = scatter_state(wstate.kpca, sub)
            ages = wstate.ages.at[:Mb].set(ages_sub)
        else:
            kpca, ages = sub, ages_sub
        return wnd.WindowState(kpca=kpca, ages=ages, clock=clock)

    # ---- stage impls: gate (core/health.py) ---------------------------------
    def _health_policy(self):
        policy = self.plan.health
        if policy is None:
            raise ValueError(
                "guarded dispatch needs a health policy — build the engine "
                "with UpdatePlan(health=health.HealthPolicy(...))")
        return policy

    def _gated_point(self, state, hstate, x_new: Array, *,
                     min_rows: int = 0):
        """Gated ingest: the offered point runs the quarantine gate
        (non-finite / outlier) before the rank-one pair fires, and an
        in-graph probe refreshes ``hstate`` — all under the same single
        dispatch, zero extra host syncs.  A rejected point returns the
        input state bitwise.  Returns ``(state, hstate)``."""
        self._health_policy()
        from repro.core import health as hl

        M = state.L.shape[0]
        Mb = self._bucket(M, max(int(state.m) + 1, min_rows))
        return hl._guarded_update_impl(state, hstate, jnp.asarray(x_new),
                                       self.spec, self.adjusted, self.plan,
                                       Mb)

    def _gated_block(self, state, hstate, xs: Array, *,
                     min_rows: int = 0):
        """Gated block ingest: per-point gate + select inside the scan,
        one probe per chunk.  Chunk cuts re-read the ACTUAL active
        count, so rejected points never push a chunk past its bucket."""
        self._health_policy()
        from repro.core import health as hl

        xs = jnp.asarray(xs)
        M = state.L.shape[0]
        n = xs.shape[0]
        i = 0
        while i < n:
            m = int(state.m)
            Mb = self._bucket(M, max(m + 1, min_rows))
            take = (min(Mb - m, n - i) if self.plan.dispatch == "bucketed"
                    else n - i)
            state, hstate = hl._guarded_scan_chunk_impl(
                state, hstate, xs[i:i + take], self.spec, self.adjusted,
                self.plan, Mb)
            i += take
        return state, hstate

    def _gated_window_point(self, wstate, hstate, x_new: Array, *,
                            window: int, min_rows: int = 0):
        """Gated sliding-window point: one arrival through the
        quarantine gate.  Rejection leaves the eigensystem, the arrival
        ring, the ages AND the clock untouched (bitwise), so the evict
        order of a stream that saw a bad point is identical to one that
        never did.  Returns ``(wstate, hstate)``."""
        self._health_policy()
        from repro.core import health as hl
        from repro.core import window as wnd

        x_new = jnp.asarray(x_new)
        M = wstate.kpca.L.shape[0]
        m = int(wstate.kpca.m)
        if int(wstate.clock) + 1 >= wnd.age_sentinel(wstate.ages.dtype) - 1:
            wstate = wnd.rebase_ages(wstate)
        if m >= window:
            Mb = self._window_bucket(M, window, min_rows)
            kpca, ages, clock, hstate = hl._guarded_window_chunk_impl(
                wstate.kpca, wstate.ages, wstate.clock, hstate,
                x_new[None], self.spec, self.adjusted, self.plan, Mb)
        else:
            Mb = self._bucket(M, max(m + 1, min_rows))
            kpca, ages, clock, hstate = hl._guarded_grow_step_impl(
                wstate.kpca, wstate.ages, wstate.clock, hstate, x_new,
                self.spec, self.adjusted, self.plan, Mb)
        return wnd.WindowState(kpca=kpca, ages=ages, clock=clock), hstate

    def _gated_window_block(self, wstate, hstate, xs: Array, *,
                            window: int, min_rows: int = 0):
        """Gated window block: growth-phase points step through the
        per-point gate (the arrival stamp is conditional, so the ring
        semantics match the point path), steady-state points fold through
        ONE guarded scan — fixed shapes, fixed collective schedule,
        clock advances only by the accepted count."""
        self._health_policy()
        from repro.core import health as hl
        from repro.core import window as wnd

        xs = jnp.asarray(xs)
        T = xs.shape[0]
        if T == 0:
            return wstate, hstate
        M = wstate.kpca.L.shape[0]
        if int(wstate.clock) + T >= wnd.age_sentinel(wstate.ages.dtype) - 1:
            wstate = wnd.rebase_ages(wstate)
        i = 0
        # Growth phase: per-point host loop — acceptance changes m, and
        # the bucket / phase decision reads it (same sync window.ingest
        # already pays per point).
        while i < T and int(wstate.kpca.m) < window:
            Mb = self._bucket(M, max(int(wstate.kpca.m) + 1, min_rows))
            kpca, ages, clock, hstate = hl._guarded_grow_step_impl(
                wstate.kpca, wstate.ages, wstate.clock, hstate, xs[i],
                self.spec, self.adjusted, self.plan, Mb)
            wstate = wnd.WindowState(kpca=kpca, ages=ages, clock=clock)
            i += 1
        if i == T:
            return wstate, hstate
        Mb = self._window_bucket(M, window, min_rows)
        kpca, ages, clock, hstate = hl._guarded_window_chunk_impl(
            wstate.kpca, wstate.ages, wstate.clock, hstate, xs[i:],
            self.spec, self.adjusted, self.plan, Mb)
        return wnd.WindowState(kpca=kpca, ages=ages, clock=clock), hstate

    # ======== legacy variant-matrix shims (deprecated) =======================
    # The pre-collapse cartesian spellings — plain/guarded/metered ×
    # point/block × plain/window.  Each is a one-line delegation that
    # wraps its arguments into a ``StreamState`` bundle, runs the
    # composed ``step``/``step_block`` pipeline, and unwraps — bitwise
    # identical by construction (the pipeline routes through the same
    # jitted impls these spellings used).  Kept only for callers not yet
    # on the bundle API.  Do NOT add new ``*_guarded``/``*_metered``
    # variants here or anywhere on Engine: add a STAGE to the pipeline
    # instead (``make lint-api`` enforces this).
    def _wstate(self, stream: "StreamState"):
        from repro.core import window as wnd

        return wnd.WindowState(kpca=stream.kpca, ages=stream.ages,
                               clock=stream.clock)

    def update(self, state, x_new: Array, *, min_rows: int = 0):
        """Deprecated spelling of ``step`` on a bare-eigensystem bundle."""
        return self.step(StreamState(kpca=state), x_new,
                         min_rows=min_rows).kpca

    def update_block(self, state, xs: Array, *, min_rows: int = 0):
        """Deprecated spelling of ``step_block`` on a bare bundle."""
        return self.step_block(StreamState(kpca=state), xs,
                               min_rows=min_rows).kpca

    def window_step(self, wstate, x_new: Array, *, window: int,
                    min_rows: int = 0):
        """One steady-state sliding-window step (m ≡ W): evict-oldest +
        ingest fused under ONE jitted dispatch at the window's bucket —
        a length-1 ``step_block`` (the point-wise ``step`` keeps the
        two-dispatch ``window.ingest`` spelling instead)."""
        return self.window_block(wstate, jnp.asarray(x_new)[None],
                                 window=window, min_rows=min_rows)

    def window_block(self, wstate, xs: Array, *, window: int,
                     min_rows: int = 0):
        """Deprecated spelling of ``step_block`` on a windowed bundle."""
        return self._wstate(self.step_block(make_stream(wstate), xs,
                                            window=window,
                                            min_rows=min_rows))

    def update_guarded(self, state, hstate, x_new: Array, *,
                       min_rows: int = 0):
        """Deprecated spelling of ``step`` on a guarded bundle."""
        out = self.step(StreamState(kpca=state, health=hstate), x_new,
                        min_rows=min_rows)
        return out.kpca, out.health

    def update_block_guarded(self, state, hstate, xs: Array, *,
                             min_rows: int = 0):
        out = self.step_block(StreamState(kpca=state, health=hstate), xs,
                              min_rows=min_rows)
        return out.kpca, out.health

    def window_ingest_guarded(self, wstate, hstate, x_new: Array, *,
                              window: int, min_rows: int = 0):
        out = self.step(make_stream(wstate, health=hstate), x_new,
                        window=window, min_rows=min_rows)
        return self._wstate(out), out.health

    def window_block_guarded(self, wstate, hstate, xs: Array, *,
                             window: int, min_rows: int = 0):
        out = self.step_block(make_stream(wstate, health=hstate), xs,
                              window=window, min_rows=min_rows)
        return self._wstate(out), out.health

    def update_metered(self, state, mstate, x_new: Array, *,
                       min_rows: int = 0):
        """Deprecated spelling of ``step`` on a metered bundle."""
        out = self.step(StreamState(kpca=state, metrics=mstate), x_new,
                        min_rows=min_rows)
        return out.kpca, out.metrics

    def update_block_metered(self, state, mstate, xs: Array, *,
                             min_rows: int = 0):
        out = self.step_block(StreamState(kpca=state, metrics=mstate), xs,
                              min_rows=min_rows)
        return out.kpca, out.metrics

    def window_block_metered(self, wstate, mstate, xs: Array, *,
                             window: int, min_rows: int = 0):
        out = self.step_block(make_stream(wstate, metrics=mstate), xs,
                              window=window, min_rows=min_rows)
        return self._wstate(out), out.metrics

    def update_guarded_metered(self, state, hstate, mstate, x_new: Array, *,
                               min_rows: int = 0):
        out = self.step(StreamState(kpca=state, health=hstate,
                                    metrics=mstate), x_new,
                        min_rows=min_rows)
        return out.kpca, out.health, out.metrics

    def update_block_guarded_metered(self, state, hstate, mstate, xs: Array,
                                     *, min_rows: int = 0):
        out = self.step_block(StreamState(kpca=state, health=hstate,
                                          metrics=mstate), xs,
                              min_rows=min_rows)
        return out.kpca, out.health, out.metrics

    def window_block_guarded_metered(self, wstate, hstate, mstate,
                                     xs: Array, *, window: int,
                                     min_rows: int = 0):
        out = self.step_block(make_stream(wstate, health=hstate,
                                          metrics=mstate), xs,
                              window=window, min_rows=min_rows)
        return self._wstate(out), out.health, out.metrics

    def window_ingest_guarded_metered(self, wstate, hstate, mstate,
                                      x_new: Array, *, window: int,
                                      min_rows: int = 0):
        out = self.step(make_stream(wstate, health=hstate,
                                    metrics=mstate), x_new,
                        window=window, min_rows=min_rows)
        return self._wstate(out), out.health, out.metrics

    def downdate_metered(self, state, mstate, i: int, *, min_rows: int = 0):
        from repro.core import telemetry as tm

        state = self.downdate(state, i, min_rows=min_rows)
        m_after = (state.kpca.m if hasattr(state, "kpca") else state.m)
        return state, tm.note_downdate(mstate, m_after)
    # ======== end legacy variant-matrix shims ================================

    def probe(self, state, hstate=None, *, ref_lam: Array | None = None):
        """Standalone in-graph health probe of any state this engine
        serves (KPCAState, WindowState or NystromState — wrapper states
        probe their ``.kpca`` block).  ``ref_lam`` folds the spectral
        staleness check into the same dispatch.  Returns a fresh/updated
        ``HealthState`` (device-resident)."""
        from repro.core import health as hl

        policy = self.plan.health or hl.DEFAULT_POLICY
        kpca = getattr(state, "kpca", state)
        if hstate is None:
            hstate = hl.init_health(kpca.L.dtype)
        if ref_lam is None:
            return hl._probe_jit(kpca, hstate, policy)
        return hl._probe_ref_jit(kpca, hstate, policy, jnp.asarray(ref_lam))

    def heal(self, state, *, level: str = "auto", rung_out: list | None = None):
        """Walk the heal ladder (polish → resync; see ``core/health``)
        on any state this engine serves.  WindowState keeps its ring and
        clock; NystromState heals the landmark eigensystem (always
        unadjusted — the K_mm block) and keeps ``Knm``/``Xrows``, after
        which the caller should re-anchor any ``TraceErrorTracker`` via
        ``tracker.resync(state)``.  Raises ``health.HealthError`` when
        the stored points are corrupt — the restore-from-checkpoint
        rung, executed by whoever owns the checkpoint directory."""
        from repro.core import health as hl

        policy = self.plan.health or hl.DEFAULT_POLICY
        if hasattr(state, "Knm"):                      # NystromState
            kpca = hl.heal_kpca(state.kpca, self.spec, False, policy,
                                level=level, rung_out=rung_out)
            return state._replace(kpca=kpca)
        if hasattr(state, "kpca"):                     # WindowState
            kpca = hl.heal_kpca(state.kpca, self.spec, self.adjusted,
                                policy, level=level, rung_out=rung_out)
            return state._replace(kpca=kpca)
        return hl.heal_kpca(state, self.spec, self.adjusted, policy,
                            level=level, rung_out=rung_out)

    # ---- low-level rank-one -----------------------------------------------
    def rank_one(self, L: Array, U: Array, v: Array, sigma: Array, m: Array
                 ) -> tuple[Array, Array]:
        """``rankone.rank_one_update`` at bucket capacity, scattered back."""
        return rank_one(L, U, v, sigma, m, plan=self.plan)

    # ---- Nyström landmarks ------------------------------------------------
    def add_landmark(self, state, x_all, x_new: Array, *,
                     min_rows: int = 0):
        """Bucketed ``nystrom.add_landmark``: the O(M³) eigensystem update
        and the O(n·M) column write both run at bucket capacity.

        ``min_rows`` is the row-support floor, exactly as in ``update``: a
        truncated-but-UNcompacted state keeps eigenvector mass on rows
        beyond m, and bucketing below that support silently discards it —
        pass the pre-truncation landmark count until the state is
        compacted (``truncate(..., compact=True)`` needs no floor).
        """
        from repro.core import nystrom

        M = state.kpca.L.shape[0]
        Mb = self._bucket(M, max(int(state.kpca.m) + 1, min_rows))
        plan = self.plan.kernel_plan()
        if Mb == M:
            return nystrom.add_landmark(state, x_all, x_new, self.spec,
                                        plan=plan)
        sub = state._replace(kpca=slice_state(state.kpca, Mb),
                             Knm=state.Knm[:, :Mb])
        sub = nystrom.add_landmark(sub, x_all, x_new, self.spec, plan=plan)
        return state._replace(kpca=scatter_state(state.kpca, sub.kpca),
                              Knm=state.Knm.at[:, :Mb].set(sub.Knm),
                              Xrows=sub.Xrows)

    def remove_landmark(self, state, j: int, *, min_rows: int = 0):
        """Bucketed ``nystrom.remove_landmark``: the eigensystem downdate
        and the Knm column shuffle both run at the bucket holding the
        current landmark count (no growth, so the bucket needs m rows,
        not m+1)."""
        from repro.core import nystrom

        M = state.kpca.L.shape[0]
        m = int(state.kpca.m)
        if m < 2:
            raise ValueError(f"remove_landmark needs at least 2 landmarks, "
                             f"got m={m}")
        if not 0 <= j < m:
            raise ValueError(f"landmark index {j} outside active range "
                             f"[0, {m})")
        Mb = self._bucket(M, max(m, min_rows, 1))
        plan = self.plan.kernel_plan()
        if Mb == M:
            return nystrom.remove_landmark(state, jnp.asarray(j, jnp.int32),
                                           self.spec, plan=plan)
        sub = state._replace(kpca=slice_state(state.kpca, Mb),
                             Knm=state.Knm[:, :Mb])
        sub = nystrom.remove_landmark(sub, jnp.asarray(j, jnp.int32),
                                      self.spec, plan=plan)
        return state._replace(kpca=scatter_state(state.kpca, sub.kpca),
                              Knm=state.Knm.at[:, :Mb].set(sub.Knm))

    def replace_landmark(self, state, x_all, j: int, x_new: Array, *,
                         min_rows: int = 0, donate: bool = False):
        """Swap landmark ``j`` for ``x_new``: remove + add fused into ONE
        jitted dispatch at the bucket (the eager slice/scatter of two
        separate bucketed calls would rival the compute at serving
        sizes).  O(M_b³ + n) against the O(n·m·d + m³ + n·M alloc)
        from-scratch rebuild — the landmark-lifecycle fast path (see
        benchmarks/bench_window.py).  The bucket needs m rows only: the
        removal frees the slot before the add writes row m−1.

        ``donate=True`` consumes the input state: the (n, M) Knm and the
        (M, M) eigenvector buffers are updated in place, so the swap's
        memory traffic is O(n + M_b²) instead of O(n·M).  Use it in the
        steady-state lifecycle (serve loop, benchmarks) where the
        pre-swap state is dead anyway; the default copies.
        """
        M = state.kpca.L.shape[0]
        m = int(state.kpca.m)
        if m < 2:
            raise ValueError(f"replace_landmark needs at least 2 "
                             f"landmarks, got m={m}")
        if not 0 <= j < m:
            raise ValueError(f"landmark index {j} outside active range "
                             f"[0, {m})")
        Mb = self._bucket(M, max(m, min_rows, 1))
        plan = self.plan.kernel_plan()
        # Mb == M still routes through the jitted impl (the slice is a
        # no-op there) so donation holds for fixed-dispatch and
        # top-bucket states too — not just sliced buckets.
        fn = (_replace_landmark_sliced_donated if donate
              else _replace_landmark_sliced)
        return fn(state, jnp.asarray(j, jnp.int32), x_new, x_all,
                  self.spec, plan, Mb)

    def offer_landmark(self, state, x: Array, *, x_all=None,
                       budget: int | None = None, admit_tol: float = 1e-3,
                       reg: float = 1e-6, min_rows: int = 0,
                       residual: float | None = None):
        """Offer one candidate landmark under ``plan.landmark_policy``.

        * ``"append"`` — the paper's §4 loop: admit every candidate until
          the budget fills, then reject.
        * ``"leverage"`` — residual-gated admission with lowest-leverage
          replacement at budget (``nystrom.consider_landmark``);
          ``residual`` forwards a precomputed ``admission_residual``.

        Returns ``(state, action)`` with action in
        {"admitted", "rejected", "replaced"}.
        """
        from repro.core import nystrom

        if self.plan.landmark_policy == "leverage":
            return nystrom.consider_landmark(
                self, state, x, x_all=x_all, budget=budget,
                admit_tol=admit_tol, reg=reg, min_rows=min_rows,
                residual=residual)
        if self.plan.landmark_policy != "append":
            raise ValueError(f"unknown landmark_policy "
                             f"{self.plan.landmark_policy!r}")
        M = state.kpca.L.shape[0]
        budget = budget if budget is not None else M - 1
        if int(state.kpca.m) < budget:
            return self.add_landmark(state, x_all, x,
                                     min_rows=min_rows), "admitted"
        return state, "rejected"

    # ---- truncation / compaction ------------------------------------------
    def truncate(self, state, k: int, *, compact: bool | None = None,
                 capacity: int | None = None):
        """Keep only the k dominant eigenpairs (paper conclusion: 'adapt the
        proposed algorithm to only maintain a subset').

        The kept eigenvector columns retain support on the pre-truncation
        rows.  ``compact`` policy:

        * ``True`` — re-express the state on its leading rows and shrink
          the arrays to the active bucket (or ``capacity``): the old large
          bucket's memory is freed.
        * ``False`` — seed-faithful truncation (old rows keep eigenvector
          mass).  Bucketed dispatch MUST then keep slicing at the OLD
          active count: pass the old m as ``min_rows`` to
          ``update``/``update_block``.  ``KPCAStream`` tracks this floor
          automatically; direct engine callers own it themselves (results
          silently degrade otherwise), and the floor does not survive a
          checkpoint — compact before saving a truncated state.
        * ``None`` (default) — ``plan.compact_shrink``, except that a
          bucketed-dispatch engine compacts at UNCHANGED capacity, so a
          bare ``engine.truncate(state, k)`` is always safe to keep
          streaming from without any ``min_rows`` bookkeeping.

        A ``NystromState`` (anything with a ``.kpca`` field) is routed
        through ``_truncate_nystrom``: its rows are OBSERVED landmarks
        with live ``Knm`` columns, so compaction is clamped to the
        row-support floor instead of dropping out-of-support mass.
        """
        if hasattr(state, "kpca"):
            return self._truncate_nystrom(state, k, compact=compact,
                                          capacity=capacity)
        keep_capacity = False
        if compact is None:
            compact = self.plan.compact_shrink
            if not compact and self.plan.dispatch == "bucketed":
                compact, keep_capacity = True, True
        M = state.L.shape[0]
        mask = rankone.active_mask(M, state.m)
        order = jnp.argsort(jnp.where(mask, -state.L, jnp.inf))
        keep = order[:k]
        L = jnp.zeros_like(state.L).at[:k].set(state.L[keep])
        U = jnp.eye(M, dtype=state.U.dtype).at[:, :k].set(state.U[:, keep])
        m = jnp.minimum(state.m, jnp.asarray(k, state.m.dtype))
        L = rankone.sentinelize(L, m, jnp.zeros((), L.dtype))
        out = state._replace(L=L, U=U, m=m)
        if compact:
            out = self.compact(out, capacity=M if keep_capacity else capacity)
        return out

    def _truncate_nystrom(self, state, k: int, *, compact: bool | None,
                          capacity: int | None):
        """Truncate a Nyström state's eigensystem without losing landmarks.

        Unlike a pure KPCA stream — whose downstream consumers only ever
        read the leading m rows — a Nyström state's rows are *observed*
        landmarks: row j of the kpca block pairs with the live column
        ``Knm[:, j]``, and ``nystrom_eigpairs``/``reconstruct_tilde``
        contract over ALL rows carrying eigenvector mass.  Plain
        ``compact`` would re-diagonalize the leading k×k block and drop
        rows k..m — silently corrupting every later reconstruction.  Here
        compaction is CLAMPED to the row-support floor r = m (the
        landmark count): the truncated rank-k system is re-diagonalized
        on all r rows (top-k spectrum plus r−k ≈ 0 eigenvalues), m stays
        r, and the capacity shrinks to the bucket holding r+1 — memory
        is freed without dropping a single observed row.  ``Knm`` columns
        follow the new capacity; its rows (the observed stream) are never
        touched.  An explicit ``capacity`` below r+1 raises.
        """
        kpca = state.kpca
        if compact is None:
            compact = (self.plan.compact_shrink
                       or self.plan.dispatch == "bucketed")
        r = int(kpca.m)                       # row-support floor: landmarks
        trunc = self.truncate(kpca, k, compact=False)
        if not compact:
            # Uncompacted: eigenvector mass stays on all r landmark rows,
            # and a bucketed engine would otherwise re-bucket at the NEW
            # m and drop it — callers own the floor: pass min_rows=r (the
            # pre-truncation landmark count) to every subsequent
            # ``add_landmark``/``update`` until the state is compacted.
            return state._replace(kpca=trunc)
        M = kpca.L.shape[0]
        cap = (capacity if capacity is not None
               else bucket_for(r + 1, max(M, r + 1), self.plan.min_bucket))
        if cap <= r:
            raise ValueError(
                f"compaction capacity {cap} would drop observed landmark "
                f"rows (row support {r}) — Nyström compaction is clamped "
                f"to the row-support floor")
        dtype = kpca.L.dtype
        mask = rankone.active_mask(M, trunc.m)
        Lm = jnp.where(mask, trunc.L, 0.0)
        Kc = jnp.matmul(trunc.U * Lm[None, :], trunc.U.T,
                        precision=MATMUL_PRECISION)[:r, :r]
        lam, vec = jnp.linalg.eigh(Kc)
        # The block has rank <= k: flush the r-k numerically-zero
        # eigenvalues to exact 0 so the Nyström pseudo-inverse consumers
        # (nystrom_eigpairs / reconstruct_tilde) deflate them cleanly.
        tol = r * jnp.finfo(dtype).eps * jnp.max(jnp.abs(lam))
        lam = jnp.where(jnp.abs(lam) <= tol, 0.0, lam)
        L = jnp.zeros((cap,), dtype).at[:r].set(lam.astype(dtype))
        U = jnp.eye(cap, dtype=dtype).at[:r, :r].set(vec.astype(dtype))
        mm = jnp.asarray(r, kpca.m.dtype)
        L = rankone.sentinelize(L, mm, jnp.zeros((), dtype))
        ncopy = min(cap, M)
        K1 = jnp.zeros((cap,), dtype).at[:ncopy].set(kpca.K1[:ncopy])
        X = jnp.zeros((cap,) + kpca.X.shape[1:],
                      kpca.X.dtype).at[:ncopy].set(kpca.X[:ncopy])
        new_kpca = kpca._replace(L=L, U=U, m=mm, K1=K1, X=X)
        n = state.Knm.shape[0]
        Knm = jnp.zeros((n, cap), state.Knm.dtype)
        Knm = Knm.at[:, :ncopy].set(state.Knm[:, :ncopy])
        return state._replace(kpca=new_kpca, Knm=Knm)

    def compact(self, state, capacity: int | None = None):
        """Re-express the active eigensystem on its leading m rows and
        re-allocate at ``capacity`` (default: the smallest bucket holding
        m+1) — the shrink half of bucketed dispatch.

        The maintained model only ever *reads* the leading m rows of the
        active columns (kernel rows, update vectors and transform queries
        are all masked beyond m), so re-diagonalizing the m×m block of the
        reconstruction is exact for every downstream consumer.  For a
        state whose support already sits in the leading rows (any stream
        that never truncated) this is a pure re-allocation; after
        ``truncate`` it also drops the out-of-support eigenvector mass,
        which is what frees the old large bucket.
        """
        M = state.L.shape[0]
        m = int(state.m)
        cap = (capacity if capacity is not None
               else bucket_for(m + 1, max(M, m + 1), self.plan.min_bucket))
        if cap <= m:
            raise ValueError(f"compaction capacity {cap} cannot hold "
                             f"{m} active pairs plus one update")
        dtype = state.L.dtype
        Kc = rankone.reconstruct(state.L, state.U, state.m)[:m, :m]
        lam, vec = jnp.linalg.eigh(Kc)
        L = jnp.zeros((cap,), dtype).at[:m].set(lam.astype(dtype))
        U = jnp.eye(cap, dtype=dtype).at[:m, :m].set(vec.astype(dtype))
        mm = jnp.asarray(m, state.m.dtype)
        L = rankone.sentinelize(L, mm, jnp.zeros((), dtype))
        ncopy = min(cap, M)
        K1 = jnp.zeros((cap,), dtype).at[:ncopy].set(state.K1[:ncopy])
        X = jnp.zeros((cap,) + state.X.shape[1:],
                      state.X.dtype).at[:ncopy].set(state.X[:ncopy])
        return state._replace(L=L, U=U, m=mm, K1=K1, X=X)


def _replace_landmark_sliced_impl(state, j: Array, x_new: Array, x_all,
                                  spec: kf.KernelSpec, plan: UpdatePlan,
                                  Mb: int):
    """slice → remove_landmark → add_landmark → scatter under one jit."""
    from repro.core import nystrom

    sub = state._replace(kpca=slice_state(state.kpca, Mb),
                         Knm=state.Knm[:, :Mb])
    sub = nystrom.replace_landmark(sub, x_all, j, x_new, spec, plan=plan)
    return state._replace(kpca=scatter_state(state.kpca, sub.kpca),
                          Knm=state.Knm.at[:, :Mb].set(sub.Knm),
                          Xrows=sub.Xrows)


_replace_landmark_sliced = jax.jit(
    _replace_landmark_sliced_impl, static_argnames=("spec", "plan", "Mb"))
# Donating spelling for the steady-state lifecycle: the O(n·M) Knm (and
# the M×M eigenvectors) update IN PLACE instead of being copied per swap,
# so a replace's memory traffic is O(n + M_b²), not O(n·M).  The caller's
# input state is consumed.
_replace_landmark_sliced_donated = jax.jit(
    _replace_landmark_sliced_impl, static_argnames=("spec", "plan", "Mb"),
    donate_argnums=(0,))


# ---------------------------------------------------- multi-tenant batch --
class StreamBatch:
    """B independent KPCA streams advanced in lockstep via vmap.

    The production-serving shape: rather than one Python loop per tenant
    (B dispatches per wall-clock step), one stacked ``KPCAState`` folds a
    point into every tenant's eigendecomposition in a single device step.
    Per-tenant active counts ``m_i`` may diverge (pass ``active`` masks).

    Cohort geometry (``cohorts=``):

    * ``"max"`` (default) — bucketed dispatch runs the whole cohort at
      the bucket of ``max_i m_i + 1``, so a cohort's cost tracks its
      largest tenant.
    * ``"bucket"`` — **bucket-homogeneous cohorts**: tenants are grouped
      by their own active bucket, and one step runs one vmapped update
      per GROUP at that group's M_b.  A mixed-size cohort (m_i spread
      ≥ the bucket ratio) then pays Σ_b |group_b|·O(M_b³) instead of
      B·O(max_b M_b³); the per-step device dispatch count equals the
      number of occupied buckets (≤ log2(M/min_bucket)+1), not B.
      Group membership migrates at bucket crossings (host-side
      regroup + re-slice, amortized like any bucket crossing).
    * ``"bucket-padded"`` — like ``"bucket"``, but each group's tenant
      axis is padded to the next power of two with inert copies of the
      group's first tenant (masked out of every step, never scattered
      back).  Each vmapped step then compiles per (pow2 group size,
      M_b) pair — at most log2(B)+1 sizes per bucket — so tenant churn
      (joins/leaves re-cutting group sizes every few steps) pays
      bounded recompiles instead of one per distinct group size, at the
      cost of ≤ 2× redundant lane compute inside a group.

    Sliding windows (``window=W``): an active tenant sitting at m = W
    first evicts its oldest point via a masked batched downdate
    (``_batched_downdate_masked`` — the decremental mirror of the update
    step) and then ingests, so per-tenant memory and cost are bounded
    forever.  Lockstep FIFO means the oldest point is always physical
    row 0 (the eviction permutation preserves survivor order), so no
    per-tenant ring is needed here — single streams carry one in
    ``core/window.py`` for checkpoint-portable eviction order.

    Unlike the single-stream engine (which slices and scatters the
    capacity-M state every step), the working state here is *bucket
    resident*: it lives at the cohort/group bucket between crossings,
    active counts are tracked on the host (exact: every folded point
    advances its tenant's m by one), and the capacity-M arrays are
    materialized only at bucket crossings or when ``.states`` is read —
    so a serving step has no slice/scatter traffic, and steps can
    pipeline.

    x0: (B, m0, d) per-tenant seed points (same m0; tenants that should
    start smaller can simply skip steps via ``active`` — their m_i, and
    with ``cohorts="bucket"`` their cost, stays behind the cohort's).
    """

    def __init__(self, x0: Array, capacity: int, spec: kf.KernelSpec, *,
                 plan: UpdatePlan = DEFAULT_PLAN, adjusted: bool = True,
                 dtype=jnp.float32, cohorts: str = "max",
                 window: int | None = None):
        import numpy as np

        from repro.core import inkpca

        x0 = jnp.asarray(x0)
        if x0.ndim != 3:
            raise ValueError(f"x0 must be (tenants, m0, d), got {x0.shape}")
        if cohorts not in ("max", "bucket", "bucket-padded"):
            raise ValueError(f"cohorts must be 'max', 'bucket' or "
                             f"'bucket-padded', got {cohorts!r}")
        if window is None:
            window = plan.window
        if window is not None:
            if not 2 <= window <= capacity:
                raise ValueError(f"window must be in [2, capacity], got "
                                 f"{window} (capacity {capacity})")
            if int(x0.shape[1]) > window:
                raise ValueError(f"seed size {x0.shape[1]} exceeds window "
                                 f"{window}")
        self.spec = spec
        self.plan = plan
        self.adjusted = adjusted
        self.capacity = capacity
        self.cohorts = cohorts
        self.window = window
        self.n_tenants = int(x0.shape[0])
        # One host-side batch init per tenant (``inkpca.gram_eigh``), then
        # stacked: a vmapped init would trace the eigh into a TPU program.
        self._full = jax.tree.map(
            lambda *leaves: jnp.stack(leaves),
            *[inkpca.init_state(x, capacity, spec, adjusted=adjusted,
                                dtype=dtype) for x in x0])
        self._sub = None          # bucket-resident working state ("max")
        self._Mb = capacity
        # Host-side upper bound on max_i m_i (exact while every step is
        # fully active; re-synced from the device at crossings).
        self._ceiling = int(x0.shape[1])
        # Exact host-side per-tenant active counts ("bucket" mode): every
        # accepted point advances its tenant by exactly one.
        self._m_host = np.full((self.n_tenants,), int(x0.shape[1]),
                               dtype=np.int64)
        self._groups: list[dict] | None = None
        # Per-tenant tally of points rejected by the non-finite gate
        # (``plan.health.quarantine``) before any device dispatch.
        self.quarantined = np.zeros((self.n_tenants,), dtype=np.int64)
        # Host-exact fold/evict tallies: every accepted point and every
        # window eviction increments its tenant's entry at the same spot
        # ``_m_host`` moves, so the metric lanes below are exact without
        # reading anything back from the device.
        self._ingest_host = np.zeros((self.n_tenants,), dtype=np.int64)
        self._evict_host = np.zeros((self.n_tenants,), dtype=np.int64)
        # Per-tenant metric lanes (core/telemetry.py): a (B,)-leaf
        # MetricsState updated once per public update/update_block call.
        self.metrics = None
        if plan.metrics:
            from repro.core import telemetry as tm

            self.metrics = tm.init_metrics_stacked(self.n_tenants, dtype)

    # ---- bucket residency ---------------------------------------------------
    def _flush(self):
        """Scatter the working state back into the capacity-M arrays."""
        if self._sub is not None:
            self._full = (_scatter_stacked(self._full, self._sub)
                          if self._Mb < self.capacity else self._sub)
            self._sub = None
        if self._groups is not None:
            for grp in self._groups:
                self._scatter_group(grp)
            self._groups = None

    # ---- bucket-homogeneous groups ("bucket"/"bucket-padded" cohorts) -------
    @property
    def _grouped(self) -> bool:
        return self.cohorts in ("bucket", "bucket-padded")

    def _tenant_bucket(self, m: int) -> int:
        if self.plan.dispatch != "bucketed":
            return self.capacity
        return bucket_for(min(m + 1, self.capacity), self.capacity,
                          self.plan.min_bucket)

    def _gather_group(self, idx) -> dict:
        import numpy as np

        Mb = self._tenant_bucket(int(self._m_host[idx].max()))
        n_real = len(idx)
        if self.cohorts == "bucket-padded" and n_real > 0:
            # Pad the tenant axis to the next power of two with inert
            # copies of the first tenant: vmapped steps compile once per
            # (pow2 size, Mb), bounding recompiles under tenant churn.
            size = 1 << (n_real - 1).bit_length()
            idx_pad = np.concatenate([idx, np.repeat(idx[:1],
                                                     size - n_real)])
        else:
            idx_pad = idx
        rows = jax.tree.map(lambda leaf: leaf[idx_pad], self._full)
        state = _slice_stacked(rows, Mb) if Mb < self.capacity else rows
        return {"Mb": Mb, "idx": idx, "idx_pad": idx_pad, "n_real": n_real,
                "state": state}

    def _scatter_group(self, grp) -> None:
        idx = grp["idx"]
        sub = jax.tree.map(lambda leaf: leaf[:grp["n_real"]], grp["state"])
        full_rows = jax.tree.map(lambda leaf: leaf[idx], self._full)
        rows = (jax.vmap(scatter_state)(full_rows, sub)
                if grp["Mb"] < self.capacity else sub)
        self._full = jax.tree.map(
            lambda leaf, r: leaf.at[idx].set(r), self._full, rows)

    def _group_mask(self, grp, host_mask):
        """Pad a per-tenant host mask to the group's (padded) lanes; pad
        lanes are always inert."""
        import numpy as np

        out = np.asarray(host_mask)[grp["idx_pad"]].copy()
        out[grp["n_real"]:] = False
        return out

    def _regroup(self):
        """(Re)partition tenants into bucket-homogeneous groups.

        Called lazily: only when no grouping exists or some tenant's next
        update would cross its group's bucket — the same crossing points
        at which the "max" cohort re-slices.
        """
        import numpy as np

        if self._groups is not None:
            stale = any(
                self._tenant_bucket(int(self._m_host[g["idx"]].max()))
                != g["Mb"]
                or len(set(self._tenant_bucket(int(mi))
                           for mi in self._m_host[g["idx"]])) > 1
                for g in self._groups)
            if not stale:
                return
            for grp in self._groups:
                self._scatter_group(grp)
            self._groups = None
        buckets = np.asarray([self._tenant_bucket(int(mi))
                              for mi in self._m_host])
        self._groups = [self._gather_group(np.nonzero(buckets == b)[0])
                        for b in sorted(set(buckets.tolist()))]

    @property
    def states(self):
        """The capacity-M stacked ``KPCAState`` (flushes the working
        bucket; use the return value of ``update`` for hot-path reads)."""
        self._flush()
        return self._full

    def _working(self, need: int):
        """Bucket-resident stacked state holding ≥ ``need`` active pairs."""
        Mb = (self.capacity if self.plan.dispatch != "bucketed"
              else bucket_for(need, self.capacity, self.plan.min_bucket))
        if self._sub is None or Mb != self._Mb:
            self._flush()
            self._Mb = Mb
            self._sub = (_slice_stacked(self._full, Mb)
                         if Mb < self.capacity else self._full)
        return self._sub

    def _need(self) -> int:
        """Rows the next update must fit, re-syncing the host ceiling from
        the device when it matters (crossing or apparent exhaustion) —
        idle tenants make the ceiling an overestimate."""
        if self.window is not None:
            # Sliding windows bound every tenant at m <= window <= capacity
            # (active tenants at the window evict before ingesting; idle
            # tenants don't grow), so exhaustion is impossible — an idle
            # tenant parked at m == capacity must not trip the raise.
            return min(self._ceiling + 1, self.capacity)
        resync = self._ceiling + 1 > self.capacity or (
            self.plan.dispatch == "bucketed" and self._sub is not None
            and bucket_for(min(self._ceiling + 1, self.capacity),
                           self.capacity, self.plan.min_bucket) > self._Mb)
        if resync:
            st = self._sub if self._sub is not None else self._full
            self._ceiling = int(jnp.max(st.m))
        if self._ceiling + 1 > self.capacity:
            raise ValueError(
                f"tenant at active count {self._ceiling} exhausted capacity "
                f"{self.capacity} — truncate/compact or re-shard the cohort")
        return self._ceiling + 1

    # ---- streaming ----------------------------------------------------------
    def _evict_mask(self, act_host):
        """Tenants whose next active ingest must first evict (window full)."""
        import numpy as np

        if self.window is None:
            return np.zeros(self.n_tenants, bool)
        return act_host & (self._m_host >= self.window)

    def _evict_grouped(self, evict, plan) -> None:
        """Masked batched downdates of the oldest point (row 0) per group."""
        for grp in self._groups:
            ge = self._group_mask(grp, evict)
            if ge.any():
                rows = jnp.zeros((len(grp["idx_pad"]),), jnp.int32)
                grp["state"] = _batched_downdate_masked(
                    grp["state"], rows, jnp.asarray(ge), self.spec,
                    self.adjusted, plan)
        self._m_host[evict] -= 1
        self._evict_host[evict] += 1
        self._ceiling = int(self._m_host.max())

    # ---- per-tenant metric lanes (core/telemetry.py) ------------------------
    def _metrics_begin(self):
        """Snapshot the host tallies at a public entry point; the commit
        applies the deltas to the metric lanes in ONE fused dispatch —
        the eigensystem dispatches above are untouched (bitwise identity
        with ``plan.metrics`` off)."""
        if self.metrics is None:
            return None
        return (self._ingest_host.copy(), self._evict_host.copy(),
                self.quarantined.copy())

    def _metrics_commit(self, snap) -> None:
        import numpy as np

        from repro.core import telemetry as tm

        if snap is None:
            return
        i0, e0, q0 = snap
        fill = (self._m_host / float(self.window) if self.window is not None
                else np.full(self.n_tenants, tm.GAUGE_UNSET))
        self.metrics = tm.note_lanes(
            self.metrics, self._ingest_host - i0, self.quarantined - q0,
            self._evict_host - e0, self._m_host, fill)

    def metrics_report(self) -> dict:
        """Host snapshot of the per-tenant metric lanes (one sync)."""
        from repro.core import telemetry as tm

        return {} if self.metrics is None else tm.metrics_report(self.metrics)

    def note_skipped_publish(self) -> None:
        """Telemetry hook for the serving loop: a publication was refused
        on health grounds (counted on every lane — the verdict is
        cohort-wide)."""
        if self.metrics is not None:
            from repro.core import telemetry as tm

            self.metrics = tm.note_skipped_publish(self.metrics)

    def note_drift(self, drift) -> None:
        """Record the last probed per-tenant spectral drift as a gauge."""
        if self.metrics is not None:
            from repro.core import telemetry as tm

            self.metrics = tm.note_drift(self.metrics, drift)

    @obs.span("kpca.update")
    def update(self, xs: Array, active: Array | None = None):
        """Fold xs[i] (shape (B, d)) into tenant i, one device step per
        occupied bucket (one total for ``cohorts="max"``) — preceded, in
        sliding-window mode, by one masked batched downdate per bucket
        for the tenants whose window is full.

        Returns the bucket-resident stacked state ("max": the whole cohort
        at the cohort bucket; grouped cohorts: the LARGEST group's state —
        use ``states``/``state_of`` for full-cohort reads).
        """
        snap = self._metrics_begin()
        out = self._update_impl(xs, active)
        self._metrics_commit(snap)
        return out

    def _update_impl(self, xs: Array, active: Array | None = None):
        import numpy as np

        xs = jnp.asarray(xs)
        plan = self.plan.kernel_plan()
        act_host = (np.ones(self.n_tenants, bool) if active is None
                    else np.asarray(active, bool))
        policy = getattr(self.plan, "health", None)
        if policy is not None and policy.quarantine:
            # Host-side non-finite gate: a poisoned lane drops out of the
            # active mask BEFORE the evict mask is computed, so a windowed
            # tenant never evicts for an ingest that does not happen, its
            # ring/clock bookkeeping (_m_host) stays untouched, and the
            # rejected point is zeroed so it cannot NaN-poison the shared
            # batched dispatch other lanes ride.
            ok = np.isfinite(np.asarray(xs)).all(axis=1)
            if not ok.all():
                self.quarantined[act_host & ~ok] += 1
                act_host = act_host & ok
                active = jnp.asarray(act_host)
                xs = jnp.where(jnp.asarray(ok)[:, None], xs, 0.0)
        evict = self._evict_mask(act_host)
        if self._grouped:
            self._m_host_pending_check(act_host, evict)
            self._regroup()
            if evict.any():
                self._evict_grouped(evict, plan)
            act_dev = None if active is None else jnp.asarray(active)
            for grp in self._groups:
                idxp = grp["idx_pad"]
                if self.cohorts == "bucket-padded":
                    ga = self._group_mask(grp, act_host)
                    if ga.any():
                        grp["state"] = _batched_update_masked(
                            grp["state"], xs[idxp], jnp.asarray(ga),
                            self.spec, self.adjusted, plan)
                elif active is None:
                    grp["state"] = _batched_update(
                        grp["state"], xs[idxp], self.spec, self.adjusted,
                        plan)
                elif act_host[idxp].any():
                    grp["state"] = _batched_update_masked(
                        grp["state"], xs[idxp], act_dev[idxp], self.spec,
                        self.adjusted, plan)
            self._m_host[act_host] += 1
            self._ingest_host[act_host] += 1
            self._ceiling = int(self._m_host.max())
            return self._groups[-1]["state"]
        if evict.any():
            # One bucket serves the evict AND the following update (a
            # larger bucket is always sound), so a steady-state window
            # step never re-slices between its two device calls.
            post_max = int((self._m_host
                            - evict.astype(self._m_host.dtype)).max())
            need = max(int(self._m_host.max()),
                       min(post_max + 1, self.capacity))
            sub = self._working(need)
            rows = jnp.zeros((self.n_tenants,), jnp.int32)
            self._sub = _batched_downdate_masked(
                sub, rows, jnp.asarray(evict), self.spec, self.adjusted,
                plan)
            self._m_host[evict] -= 1
            self._evict_host[evict] += 1
            self._ceiling = int(self._m_host.max())
            sub = self._sub
        else:
            sub = self._working(self._need())
        if active is None:
            self._sub = _batched_update(sub, xs, self.spec, self.adjusted,
                                        plan)
            self._m_host += 1
            self._ingest_host += 1
        else:
            self._sub = _batched_update_masked(sub, xs, jnp.asarray(active),
                                               self.spec, self.adjusted,
                                               plan)
            act = np.asarray(active, bool)
            self._m_host[act] += 1
            self._ingest_host[act] += 1
        self._ceiling += 1
        return self._sub

    def _steady_window_scan(self, xs: Array, mask_host, plan: UpdatePlan):
        """Fold a whole block of evict+ingest pairs for the lanes in
        ``mask_host`` (each at m ≡ W) — one scanned dispatch per cohort
        group; lanes outside the mask pass through untouched."""
        import numpy as np

        # Every masked lane folds (and therefore evicts) one point per
        # scanned step; m is invariant at W so only the tallies move.
        mk = np.asarray(mask_host, bool)
        self._ingest_host[mk] += int(xs.shape[0])
        self._evict_host[mk] += int(xs.shape[0])
        if self._grouped:
            self._regroup()
            out = None
            for grp in self._groups:
                ga = self._group_mask(grp, mask_host)
                if ga.any():
                    grp["state"] = _batched_window_scan_masked(
                        grp["state"], xs[:, grp["idx_pad"]],
                        jnp.asarray(ga), self.spec, self.adjusted, plan)
                    out = grp["state"]
            return out if out is not None else self._groups[-1]["state"]
        sub = self._working(max(int(self._m_host.max()), 1))
        self._sub = _batched_window_scan_masked(
            sub, xs, jnp.asarray(mask_host), self.spec, self.adjusted, plan)
        return self._sub

    def _m_host_pending_check(self, act_host, evict=None) -> None:
        """Raise on capacity exhaustion BEFORE mutating any state.
        ``evict`` marks tenants whose ingest evicts first (window mode),
        so their net growth is zero."""
        after = self._m_host + act_host.astype(self._m_host.dtype)
        if evict is not None:
            after = after - evict.astype(self._m_host.dtype)
        if (after > self.capacity).any():
            worst = int(self._m_host.max())
            raise ValueError(
                f"tenant at active count {worst} exhausted capacity "
                f"{self.capacity} — truncate/compact or re-shard the cohort")

    def update_block(self, xs: Array):
        """Stream a (T, B, d) block: scan over T with tenants vmapped per
        step; chunks are cut at bucket crossings (any group's, in grouped
        cohort modes).  Window mode: tenants still below their window
        step point-by-point (each step may evict, a host-side dispatch
        decision), but once EVERY tenant sits at m ≡ W the remaining
        steps are fixed-shape evict+ingest pairs and fold through ONE
        scanned dispatch per cohort group
        (``_batched_window_scan_masked``) — the multi-tenant mirror of
        ``Engine.window_block``'s steady state.

        With ``plan.health.quarantine`` the block is cut at the steps
        that carry a non-finite point: maximal clean runs keep the
        scanned block path, poisoned steps route through the per-point
        ``update`` gate (which drops only the offending lanes and tallies
        them in ``quarantined``)."""
        snap = self._metrics_begin()
        out = self._update_block_impl(xs)
        self._metrics_commit(snap)
        return out

    def _update_block_impl(self, xs: Array):
        import numpy as np

        xs = jnp.asarray(xs)
        T = xs.shape[0]
        policy = getattr(self.plan, "health", None)
        if policy is not None and policy.quarantine:
            finite = np.isfinite(np.asarray(xs)).all(axis=(1, 2))
            if not bool(finite.all()):
                out = None
                i = 0
                while i < T:
                    if finite[i]:
                        j = i + 1
                        while j < T and finite[j]:
                            j += 1
                        out = self._update_block_clean(xs[i:j])
                        i = j
                    else:
                        out = self._update_impl(xs[i])
                        i += 1
                return out
        return self._update_block_clean(xs)

    def _update_block_clean(self, xs: Array):
        """``update_block`` body for an all-finite block (see above)."""
        import numpy as np

        T = xs.shape[0]
        if self.window is not None:
            # Mixed-cohort windowed blocks: tenant lanes are disjoint, so
            # the two phases split by LANE, not by time.  Tenants already
            # sitting at m ≡ W fold the ENTIRE block through one scanned
            # dispatch per group immediately (their active counts are
            # frozen — evict+ingest nets zero, no bucket crossing can
            # occur); only the growing lanes step point-by-point (each
            # step may evict, a host-side dispatch decision), and once
            # every grower reaches W their remaining steps scan too.  A
            # mixed cohort no longer drags its steady majority through
            # per-point dispatches.
            plan = self.plan.kernel_plan()
            steady = np.asarray(self._m_host >= self.window)
            grow = ~steady
            out = None
            if steady.any():
                out = self._steady_window_scan(xs, steady, plan)
            if grow.any():
                act = None if not steady.any() else jnp.asarray(grow)
                t = 0
                while t < T and int(self._m_host[grow].min()) < self.window:
                    out = self._update_impl(xs[t], active=act)
                    t += 1
                if t < T:
                    out = self._steady_window_scan(xs[t:], grow, plan)
            return out
        i = 0
        if self._grouped:
            ones = np.ones(self.n_tenants, bool)
            plan = self.plan.kernel_plan()
            while i < T:
                self._m_host_pending_check(ones)
                self._regroup()
                take = min(min(g["Mb"] - int(self._m_host[g["idx"]].max())
                               for g in self._groups), T - i)
                for grp in self._groups:
                    blk = xs[i:i + take][:, grp["idx_pad"]]
                    if self.cohorts == "bucket-padded":
                        ga = self._group_mask(grp, ones)
                        grp["state"] = _batched_scan_masked(
                            grp["state"], blk, jnp.asarray(ga), self.spec,
                            self.adjusted, plan)
                    else:
                        grp["state"] = _batched_scan(
                            grp["state"], blk, self.spec, self.adjusted,
                            plan)
                self._m_host += take
                self._ingest_host += take
                i += take
            self._ceiling = int(self._m_host.max())
            return self._groups[-1]["state"]
        while i < T:
            sub = self._working(self._need())
            # Chunk at the working bucket even when it is the capacity rung,
            # so _need() raises on exhaustion instead of clamping writes.
            take = min(self._Mb - self._ceiling, T - i)
            self._sub = _batched_scan(sub, xs[i:i + take], self.spec,
                                      self.adjusted, self.plan.kernel_plan())
            self._ceiling += take
            self._m_host += take
            self._ingest_host += take
            i += take
        return self._sub

    def transform(self, q: Array, n_components: int) -> Array:
        """Project per-tenant query batches q: (B, nq, d) -> (B, nq, k)."""
        q = jnp.asarray(q)
        fn = partial(transform_state, spec=self.spec, adjusted=self.adjusted,
                     n_components=n_components, plan=self.plan)
        if self._grouped and self._groups is not None:
            out = None
            for grp in self._groups:
                yg = jax.vmap(fn)(grp["state"], q[grp["idx_pad"]])
                yg = yg[:grp["n_real"]]
                if out is None:
                    out = jnp.zeros((self.n_tenants,) + yg.shape[1:],
                                    yg.dtype)
                out = out.at[grp["idx"]].set(yg)
            return out
        st = self._sub if self._sub is not None else self._full
        return jax.vmap(fn)(st, q)

    def working_states(self) -> list:
        """The bucket-resident working state(s) without flushing: one
        stacked state per occupied bucket group (grouped cohorts), else
        the single cohort state.  For hot-path synchronization
        (``jax.block_until_ready``) and inspection."""
        if self._grouped and self._groups is not None:
            return [g["state"] for g in self._groups]
        return [self._sub if self._sub is not None else self._full]

    def health_summary(self) -> dict:
        """Host-side quarantine tally (``plan.health.quarantine``): total
        and per-tenant counts of points rejected by the non-finite gate
        before any device dispatch."""
        return {"quarantined": int(self.quarantined.sum()),
                "quarantined_per_tenant": self.quarantined.copy()}

    @obs.span("kpca.probe")
    def probe_all(self, ref_lam=None):
        """Vmapped in-graph health probe over every tenant's working
        state — no flush, one probe dispatch per occupied bucket group.
        Returns host arrays ``(healthy, drift)`` of shape (B,); ``drift``
        is None unless ``ref_lam`` (a (B, C) frozen top spectrum, e.g.
        the one recorded at the last publication) is given, in which case
        it carries each tenant's relative spectral drift — the staleness
        signal for drift-triggered publication."""
        import numpy as np

        from repro.core import health as hl

        policy = getattr(self.plan, "health", None) or hl.DEFAULT_POLICY
        healthy = np.zeros(self.n_tenants, bool)
        drift = None if ref_lam is None else np.zeros(self.n_tenants, float)
        ref = None if ref_lam is None else jnp.asarray(ref_lam)

        def one(st, lanes, idx):
            # lanes: tenant id per stacked lane (repeats pad the group);
            # the first len(idx) lanes are the real tenants.
            h0 = hl.init_health(st.L.dtype)
            hb = jax.vmap(lambda s: hl.probe(s, h0, policy))(st)
            ok = np.asarray(jax.vmap(lambda h: hl.verdict(h, policy))(hb))
            healthy[idx] = ok[:len(idx)]
            if ref is not None:
                dr = np.asarray(jax.vmap(hl.spectral_drift)(
                    st, ref[np.asarray(lanes)]))
                drift[idx] = dr[:len(idx)]

        if self._grouped and self._groups is not None:
            for grp in self._groups:
                one(grp["state"], np.asarray(grp["idx_pad"]),
                    np.asarray(grp["idx"]))
        else:
            st = self._sub if self._sub is not None else self._full
            idx = np.arange(self.n_tenants)
            one(st, idx, idx)
        return healthy, drift

    @obs.span("kpca.heal")
    def heal(self, *, level: str = "auto") -> int:
        """Walk the heal ladder (``health.heal_kpca``) over the cohort:
        probe every tenant, flush, and heal the unhealthy ones in place
        ("auto"; a forced ``level`` heals all).  Returns the number of
        tenants healed.  ``health.HealthError`` propagates — the
        restore-from-checkpoint rung belongs to the caller, who owns the
        checkpoint directory."""
        import numpy as np

        from repro.core import health as hl

        policy = getattr(self.plan, "health", None) or hl.DEFAULT_POLICY
        if level == "auto":
            healthy, _ = self.probe_all()
            todo = np.nonzero(~healthy)[0]
        else:
            todo = np.arange(self.n_tenants)
        if len(todo) == 0:
            return 0
        self._flush()
        full = self._full
        rungs = np.zeros((2, self.n_tenants), np.int64)  # polish / resync
        for i in todo:
            st = jax.tree.map(lambda leaf: leaf[int(i)], full)
            rung_out: list = []
            st = hl.heal_kpca(st, self.spec, self.adjusted, policy,
                              level=level, rung_out=rung_out)
            if rung_out and rung_out[-1] in ("polish", "resync"):
                rungs[0 if rung_out[-1] == "polish" else 1, int(i)] += 1
            full = jax.tree.map(lambda fl, sl: fl.at[int(i)].set(sl),
                                full, st)
        self._full = full
        if self.metrics is not None and rungs.any():
            from repro.core import telemetry as tm

            self.metrics = self.metrics._replace(
                heals_polish=self.metrics.heals_polish
                + jnp.asarray(rungs[0], jnp.int32),
                heals_resync=self.metrics.heals_resync
                + jnp.asarray(rungs[1], jnp.int32))
        return len(todo)

    @obs.span("kpca.snapshot")
    def publish(self, n_components: int | None = None):
        """Publish per-tenant ``serving.ServingSnapshot``s (stacked on the
        tenant axis) from the current working state — the decoupled-serve
        read path: queries batch against the returned snapshots
        (``serving.query_batch``) while subsequent updates keep folding
        into the working state A.  Default width is
        ``plan.serve_components``.  "max" cohorts publish from the
        bucket-resident state (snapshot capacity = the cohort bucket);
        grouped cohorts flush first so one stacked snapshot covers every
        tenant."""
        from repro.core import serving

        nc = int(self.plan.serve_components if n_components is None
                 else n_components)
        self._serve_gen = getattr(self, "_serve_gen", -1) + 1
        gen = jnp.asarray(self._serve_gen, jnp.int32)
        if self.metrics is not None:
            from repro.core import telemetry as tm

            self.metrics = tm.note_publish(self.metrics, self._serve_gen)
        if self._grouped:
            st = self.states
        else:
            st = self._sub if self._sub is not None else self._full
        return jax.vmap(lambda s: serving.publish_transform(
            s, n_components=nc, adjusted=self.adjusted, generation=gen))(st)

    def state_of(self, i: int):
        """Unstack tenant i's capacity-M state (checkpoint convenience)."""
        return jax.tree.map(lambda leaf: leaf[i], self.states)
