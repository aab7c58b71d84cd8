"""Incremental kernel PCA (paper §3, Algorithms 1 & 2).

State is fixed-capacity (capacity M, active count m) so a whole stream of
updates compiles once; see ``rankone.py`` for the padding invariants.

* ``update_unadjusted``  — Algorithm 1: expansion + 2 rank-one updates of the
  raw kernel matrix K.
* ``update_adjusted``    — Algorithm 2: 2 mean-adjustment updates of K', then
  expansion + 2 updates for the new row/column (4 rank-one updates total).

Both consume a precomputed kernel row ``a = [k(x_i, x_new)]`` and diagonal
value ``k_new = k(x_new, x_new)``; ``KPCAStream`` wires in the kernel-function
evaluation and an optional Pallas gram-row kernel, and ``update_stream`` runs
a scan over a block of points (one compilation, sequential semantics).
"""
from __future__ import annotations

from functools import partial
from typing import Literal, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as eng
from repro.core import kernels_fn as kf
from repro.core import rankone

Array = jax.Array


class KPCAState(NamedTuple):
    """Fixed-capacity incremental KPCA state.

    L:  (M,)   eigenvalues (ascending; sentinels above the active spectrum)
    U:  (M,M)  eigenvectors in columns (identity on inactive columns)
    m:  ()     active count (int32)
    S:  ()     sum of all entries of the *unadjusted* K_mm          (Alg. 2)
    K1: (M,)   row sums K_mm @ 1_m, zero-padded                     (Alg. 2)
    X:  (M,d)  stored data points (needed to evaluate kernel rows)
    """

    L: Array
    U: Array
    m: Array
    S: Array
    K1: Array
    X: Array


def gram_eigh(K: Array) -> tuple[Array, Array]:
    """Ascending eigendecomposition of a symmetric gram matrix for a
    batch (re)initialisation, in K's dtype.

    A concrete K is decomposed on the host by LAPACK in float64.  A TPU
    has no native eigh: ``jnp.linalg.eigh`` lowers there to a QDWH
    spectral divide-and-conquer whose compile takes minutes and tens of
    GiB of host memory at the sizes a chip holds (compiling the M=2048
    one for a v5e passes 8 GiB of host memory; M=8192 ran a 40 GiB host
    out of memory), while the host decomposition of an M=8192 gram takes
    seconds and is more accurate.  There is no traced path: under jit or
    vmap the conversion to a host array raises."""
    lam, vec = np.linalg.eigh(np.asarray(K, np.float64))
    return jnp.asarray(lam, K.dtype), jnp.asarray(vec, K.dtype)


def init_state(x0: Array, capacity: int, spec: kf.KernelSpec,
               *, adjusted: bool, dtype=jnp.float32) -> KPCAState:
    """Batch-initialize from m0 >= 1 seed points (eigh of the small gram)."""
    m0, d = x0.shape
    assert m0 <= capacity
    x0 = x0.astype(dtype)
    K0 = kf.gram_block(x0, x0, spec=spec)
    S = jnp.sum(K0)
    K1 = jnp.sum(K0, axis=1)
    Keff = kf.center_gram(K0) if adjusted else K0
    lam, vec = gram_eigh(Keff)

    M = capacity
    L = jnp.zeros((M,), dtype)
    U = jnp.eye(M, dtype=dtype)
    L = L.at[:m0].set(lam.astype(dtype))
    U = U.at[:m0, :m0].set(vec.astype(dtype))
    m = jnp.asarray(m0, jnp.int32)
    L = rankone.sentinelize(L, m, jnp.zeros((), dtype))

    X = jnp.zeros((M, d), dtype).at[:m0].set(x0)
    K1p = jnp.zeros((M,), dtype).at[:m0].set(K1.astype(dtype))
    return KPCAState(L=L, U=U, m=m, S=S.astype(dtype), K1=K1p, X=X)


def _masked_row(state: KPCAState, x_new: Array, spec: kf.KernelSpec) -> tuple[Array, Array]:
    """Kernel row against stored points, zeroed beyond the active count.
    (Canonical implementation lives in the engine layer.)"""
    return eng.masked_row(state, x_new, spec)


@partial(jax.jit, static_argnames=("plan",))
def update_unadjusted(state: KPCAState, a: Array, k_new: Array, x_new: Array,
                      *, plan: eng.UpdatePlan = eng.DEFAULT_PLAN
                      ) -> KPCAState:
    """Algorithm 1: K_{m,m} -> K_{m+1,m+1} via expansion + 2 rank-one updates."""
    M = state.L.shape[0]
    m = state.m
    kn = jnp.maximum(k_new, jnp.finfo(state.L.dtype).tiny)  # sigma = 4/k guard

    # Bookkeeping for the unadjusted matrix (shared with Alg. 2 / Nyström).
    sum_a = jnp.sum(a)
    S2 = state.S + 2.0 * sum_a + k_new
    K1 = jnp.where(rankone.active_mask(M, m), state.K1 + a, 0.0)
    K1 = K1.at[m].set(sum_a + k_new)
    X = jax.lax.dynamic_update_slice(state.X, x_new[None].astype(state.X.dtype),
                                     (m, jnp.zeros((), m.dtype)))

    # Expansion: eigenpair (k/4, e_m), then the two updates from paper eq. (2).
    L, U, m1 = rankone.expand_eigensystem(state.L, state.U, kn / 4.0, m)
    v1 = a.at[m].set(kn / 2.0)
    v2 = a.at[m].set(kn / 4.0)
    sigma = 4.0 / kn
    L, U = eng.apply_pair(L, U, v1, sigma, v2, -sigma, m1, plan=plan)
    return KPCAState(L=L, U=U, m=m1, S=S2, K1=K1, X=X)


@partial(jax.jit, static_argnames=("plan",))
def update_adjusted(state: KPCAState, a: Array, k_new: Array, x_new: Array,
                    *, plan: eng.UpdatePlan = eng.DEFAULT_PLAN
                    ) -> KPCAState:
    """Algorithm 2: K'_{m,m} -> K'_{m+1,m+1} via 4 rank-one updates.

    Follows the paper's derivation (§3.1.2); Alg. 2 line 4 contains an
    erratum (the square on m(m+1)) — we use the derived
    u = K1/(m(m+1)) - a/(m+1) + C/2 * 1_m, verified against direct
    construction of K' in the tests.
    """
    M = state.L.shape[0]
    m = state.m
    mf = m.astype(state.L.dtype)
    mask_m = rankone.active_mask(M, m)

    # --- Step 1: mean-adjustment of the existing m×m block (2 updates). ---
    sum_a = jnp.sum(a)
    S2 = state.S + 2.0 * sum_a + k_new
    C = -state.S / mf**2 + S2 / (mf + 1.0) ** 2
    u = (state.K1 / (mf * (mf + 1.0)) - a / (mf + 1.0) + 0.5 * C)
    u = jnp.where(mask_m, u, 0.0)
    ones_u_p = jnp.where(mask_m, 1.0 + u, 0.0)
    ones_u_m = jnp.where(mask_m, 1.0 - u, 0.0)
    half = jnp.asarray(0.5, state.L.dtype)
    L, U = eng.apply_pair(state.L, state.U, ones_u_p, half, ones_u_m, -half,
                          m, plan=plan)

    # --- Step 2: bookkeeping updates (paper lines 7-9). ---
    K1 = jnp.where(mask_m, state.K1 + a, 0.0)
    K1 = K1.at[m].set(sum_a + k_new)
    m_new_f = mf + 1.0

    # --- Step 3: new centered row/column v (paper line 10). ---
    k_vec = a.at[m].set(k_new)
    mask_m1 = rankone.active_mask(M, m + 1)
    v = k_vec - (jnp.sum(k_vec) + K1 - S2 / m_new_f) / m_new_f
    v = jnp.where(mask_m1, v, 0.0)
    v0 = v[m]
    v0 = jnp.where(jnp.abs(v0) < jnp.finfo(L.dtype).eps,
                   jnp.finfo(L.dtype).eps, v0)  # sigma = 4/v0 guard

    # --- Step 4: expansion + 2 updates (paper eq. (3)). ---
    L, U, m1 = rankone.expand_eigensystem(L, U, v0 / 4.0, m)
    v1 = v.at[m].set(v0 / 2.0)
    v2 = v.at[m].set(v0 / 4.0)
    sigma = 4.0 / v0
    L, U = eng.apply_pair(L, U, v1, sigma, v2, -sigma, m1, plan=plan)

    X = jax.lax.dynamic_update_slice(state.X, x_new[None].astype(state.X.dtype),
                                     (m, jnp.zeros((), m.dtype)))
    return KPCAState(L=L, U=U, m=m1, S=S2, K1=K1, X=X)


# ------------------------------------------------------------ fused ingest --
# ``update_unadjusted``/``update_adjusted`` consume a precomputed kernel
# row and then let the rank-one machinery re-read U for every projection
# Uᵀv.  The ingest_* variants below instead run the fused
# ``kernels/rbf_gram.krow_project`` prologue: ONE pass over U produces the
# masked row a AND the projections of every update vector that lives in the
# pre-update basis.  The z vectors handed to ``eng.apply_pair`` are exact
# identities, not approximations:
#
# * pre-expansion, Uᵀe_m = e_m (column m is an identity column and active
#   columns vanish on row m), so the expansion pair's projections are
#   z = (Uᵀa).at[m].set(kn/2 | kn/4) permuted by the expansion sort;
# * Algorithm 2's mean-adjustment vectors 1±u are affine in (a, 1_m, K1),
#   so their projections are the same affine combination of the three
#   projected columns.
#
# Algorithm 2's second (expansion) pair cannot ride the krow prologue —
# its basis is the post-rotation U₁, which does not exist until the first
# pair runs — but its projection is still one rect-pruned
# ``eigvec_update.project_vectors`` pass (Uᵀ[v₁|v₂]) rather than a dense
# einsum, so no per-step dense pass over the (M, M) eigenvectors remains.


@partial(jax.jit, static_argnames=("spec", "plan"))
def ingest_unadjusted(state: KPCAState, x_new: Array, *, spec: kf.KernelSpec,
                      plan: eng.UpdatePlan = eng.DEFAULT_PLAN) -> KPCAState:
    """Algorithm 1 with the fused kernel-row prologue (plan.fuse_krow)."""
    from repro.kernels.rbf_gram import ops as kops

    M = state.L.shape[0]
    m = state.m
    dtype = state.L.dtype
    x_new = x_new.astype(state.X.dtype)
    k_new = kf.kernel_diag(x_new[None], spec=spec)[0].astype(dtype)
    kn = jnp.maximum(k_new, jnp.finfo(dtype).tiny)  # sigma = 4/k guard

    aux = jnp.zeros((M, 0), dtype)
    with jax.named_scope("kpca.krow"):
        a, P = kops.krow_project(state.U, state.X, x_new, aux, m, spec=spec)
    p = P[:, 0]                                     # Uᵀa, pre-expansion

    sum_a = jnp.sum(a)
    S2 = state.S + 2.0 * sum_a + k_new
    K1 = jnp.where(rankone.active_mask(M, m), state.K1 + a, 0.0)
    K1 = K1.at[m].set(sum_a + k_new)
    X = jax.lax.dynamic_update_slice(state.X,
                                     x_new[None].astype(state.X.dtype),
                                     (m, jnp.zeros((), m.dtype)))

    L, perm, m1 = rankone.expand_eigensystem_perm(state.L, kn / 4.0, m)
    U = state.U[:, perm]
    v1 = a.at[m].set(kn / 2.0)
    v2 = a.at[m].set(kn / 4.0)
    # Uᵀe_m = e_m and (Uᵀa)[m] = a[m] = 0 pre-expansion, so the expanded
    # basis's projections are p with slot m overwritten, permuted.
    z1 = p.at[m].set(kn / 2.0)[perm]
    z2 = p.at[m].set(kn / 4.0)[perm]
    sigma = 4.0 / kn
    L, U = eng.apply_pair(L, U, v1, sigma, v2, -sigma, m1, plan=plan,
                          z1=z1, z2=z2)
    return KPCAState(L=L, U=U, m=m1, S=S2, K1=K1, X=X)


@partial(jax.jit, static_argnames=("spec", "plan"))
def ingest_adjusted(state: KPCAState, x_new: Array, *, spec: kf.KernelSpec,
                    plan: eng.UpdatePlan = eng.DEFAULT_PLAN) -> KPCAState:
    """Algorithm 2 with the fused kernel-row prologue (plan.fuse_krow).

    The mean-adjustment pair's projections come from the fused kernel
    (z_± = Uᵀ1_m ± Uᵀu as affine combinations of the projected columns);
    the expansion pair projects against the rotated U₁ through the
    rect-pruned ``project_vectors`` kernel.
    """
    from repro.kernels.rbf_gram import ops as kops

    M = state.L.shape[0]
    m = state.m
    dtype = state.L.dtype
    mf = m.astype(dtype)
    mask_m = rankone.active_mask(M, m)
    x_new = x_new.astype(state.X.dtype)
    k_new = kf.kernel_diag(x_new[None], spec=spec)[0].astype(dtype)

    # One fused pass: a plus Uᵀ[a | 1_m | K1] (the kernel masks rows >= m).
    aux = jnp.stack([jnp.ones((M,), dtype), state.K1], axis=1)
    with jax.named_scope("kpca.krow"):
        a, P = kops.krow_project(state.U, state.X, x_new, aux, m, spec=spec)
    pa, p1, pk1 = P[:, 0], P[:, 1], P[:, 2]

    # --- Step 1: mean-adjustment of the existing m×m block (2 updates). ---
    sum_a = jnp.sum(a)
    S2 = state.S + 2.0 * sum_a + k_new
    C = -state.S / mf**2 + S2 / (mf + 1.0) ** 2
    u = (state.K1 / (mf * (mf + 1.0)) - a / (mf + 1.0) + 0.5 * C)
    u = jnp.where(mask_m, u, 0.0)
    ones_u_p = jnp.where(mask_m, 1.0 + u, 0.0)
    ones_u_m = jnp.where(mask_m, 1.0 - u, 0.0)
    zu = pk1 / (mf * (mf + 1.0)) - pa / (mf + 1.0) + 0.5 * C * p1
    half = jnp.asarray(0.5, dtype)
    L, U = eng.apply_pair(state.L, state.U, ones_u_p, half, ones_u_m, -half,
                          m, plan=plan, z1=p1 + zu, z2=p1 - zu)

    # --- Steps 2-4: identical to ``update_adjusted`` (expansion unfused). ---
    K1 = jnp.where(mask_m, state.K1 + a, 0.0)
    K1 = K1.at[m].set(sum_a + k_new)
    m_new_f = mf + 1.0

    k_vec = a.at[m].set(k_new)
    mask_m1 = rankone.active_mask(M, m + 1)
    v = k_vec - (jnp.sum(k_vec) + K1 - S2 / m_new_f) / m_new_f
    v = jnp.where(mask_m1, v, 0.0)
    v0 = v[m]
    v0 = jnp.where(jnp.abs(v0) < jnp.finfo(L.dtype).eps,
                   jnp.finfo(L.dtype).eps, v0)  # sigma = 4/v0 guard

    L, U, m1 = rankone.expand_eigensystem(L, U, v0 / 4.0, m)
    v1 = v.at[m].set(v0 / 2.0)
    v2 = v.at[m].set(v0 / 4.0)
    sigma = 4.0 / v0
    # The expansion pair's basis is the rotated U₁ (it does not exist
    # before the first pair runs), so its projections cannot ride the
    # krow prologue — but they are still one rect-pruned kernel pass
    # (Uᵀ[v₁|v₂]) instead of the dense einsum rank_one_update_pair would
    # otherwise run.  Post-expansion both v's vanish on rows >= m1 and
    # inactive columns are identity columns on that masked region, so the
    # pruned projection is exact.
    from repro.kernels.eigvec_update import ops as eops
    with jax.named_scope("kpca.krow"):
        Z = eops.project_vectors(U, jnp.stack([v1, v2], axis=1), m1)
    L, U = eng.apply_pair(L, U, v1, sigma, v2, -sigma, m1, plan=plan,
                          z1=Z[:, 0], z2=Z[:, 1])

    X = jax.lax.dynamic_update_slice(state.X,
                                     x_new[None].astype(state.X.dtype),
                                     (m, jnp.zeros((), m.dtype)))
    return KPCAState(L=L, U=U, m=m1, S=S2, K1=K1, X=X)


class KPCAStream:
    """User-facing streaming driver — a thin shell over ``engine.Engine``.

    All dispatch decisions (bucket selection, fused-pair vs sequential,
    merge fallback, compaction) live in the engine's ``UpdatePlan``; pass
    one directly via ``plan=`` or use the legacy keyword spellings
    (``method``/``matmul``/``iters``/``dispatch``/``min_bucket``), which
    are folded into a plan here and nowhere else.

    ``dispatch="bucketed"`` runs each step at the smallest power-of-two
    bucket capacity holding the active set, so per-update cost scales with
    m instead of the fixed capacity M (one extra compilation per bucket
    visited; see engine.py for the crossing/retrace cost model).

    ``window=W`` turns the stream into a **sliding window** over the
    trailing W points: ingesting past a full window first evicts the
    oldest point via the decremental pipeline (``core/downdate.py``), so
    memory and per-step cost are bounded on unbounded streams.  In this
    mode ``self.state`` is a ``window.WindowState`` — the eigensystem
    plus a FIFO arrival ring, so eviction order survives checkpoint
    round-trips; ``kpca_state`` always exposes the inner ``KPCAState``.
    """

    def __init__(self, x0: Array, capacity: int, spec: kf.KernelSpec, *,
                 adjusted: bool = True, plan: eng.UpdatePlan | None = None,
                 method: Literal["gu", "bns"] = "gu",
                 matmul: Literal["jnp", "pallas", "jnp2", "pallas2"] = "jnp",
                 iters: int | None = None, dtype=jnp.float32,
                 dispatch: Literal["fixed", "bucketed"] = "fixed",
                 min_bucket: int | None = None,
                 window: int | None = None):
        from repro.core import window as wnd

        if plan is None:
            plan = eng.UpdatePlan(
                method=method, matmul=matmul, iters=iters, dispatch=dispatch,
                min_bucket=(min_bucket if min_bucket is not None
                            else eng.DEFAULT_MIN_BUCKET),
                window=window)
        if window is None:
            window = plan.window
        self.spec = spec
        self.adjusted = adjusted
        self.plan = plan
        self.window = window
        self.engine = eng.Engine(spec, plan, adjusted=adjusted)
        if window is not None:
            if not 2 <= window <= capacity:
                raise ValueError(f"window must be in [2, capacity], got "
                                 f"{window} (capacity {capacity})")
            if int(jnp.asarray(x0).shape[0]) > window:
                raise ValueError(f"seed size {jnp.asarray(x0).shape[0]} "
                                 f"exceeds window {window}")
            self.state = wnd.init_window(x0, capacity, spec,
                                         adjusted=adjusted, dtype=dtype)
        else:
            self.state = init_state(x0, capacity, spec, adjusted=adjusted,
                                    dtype=dtype)
        # Row-support floor for bucket selection: a truncated, uncompacted
        # state keeps eigenvector mass on rows beyond m (see Engine.truncate).
        self._min_rows = 0
        # Self-healing layer (core/health.py): with plan.health set, every
        # update routes through the guarded dispatches — input quarantine
        # plus in-graph probes riding along in self.health.
        self.health = None
        if plan.health is not None:
            from repro.core import health as hl
            self.health = hl.init_health(self.kpca_state.L.dtype)
        # Telemetry lane (core/telemetry.py): with plan.metrics set, a
        # MetricsState rides the stream.  The eigensystem still goes
        # through the IDENTICAL dispatches — each update is followed by
        # one tiny separate note dispatch, so metrics-on state is bitwise
        # metrics-off state.
        self.metrics = None
        if plan.metrics:
            from repro.core import telemetry as tm
            self.metrics = tm.init_metrics(self.kpca_state.L.dtype)

    @property
    def kpca_state(self) -> KPCAState:
        """The eigensystem state, regardless of windowing."""
        return self.state.kpca if self.window is not None else self.state

    def _bundle(self) -> eng.StreamState:
        """The stream's whole mutable state as ONE pipeline bundle: the
        eigensystem, plus the arrival ring / HealthState / MetricsState
        exactly when the plan carries the matching stage."""
        return eng.make_stream(self.state, health=self.health,
                               metrics=self.metrics)

    def _unbundle(self, s: eng.StreamState):
        """Write an advanced bundle back into the stream's attributes and
        return ``self.state`` (the legacy return convention)."""
        if self.window is not None:
            from repro.core import window as wnd
            self.state = wnd.WindowState(kpca=s.kpca, ages=s.ages,
                                         clock=s.clock)
        else:
            self.state = s.kpca
        self.health = s.health
        self.metrics = s.metrics
        return self.state

    def update(self, x_new: Array):
        """One point through the composed gate→evict|ingest→note pipeline
        (``engine.Engine.step``) — the bundle's structure, set from the
        plan at construction, selects the stages."""
        return self._unbundle(self.engine.step(
            self._bundle(), x_new, window=self.window,
            min_rows=self._min_rows))

    def downdate(self, i: int):
        """Remove point ``i`` (physical row) from the stream."""
        if self.window is not None:
            from repro.core import window as wnd
            self.state = wnd.evict(self.engine, self.state, i,
                                   min_rows=self._min_rows)
        else:
            self.state = self.engine.downdate(self.state, i,
                                              min_rows=self._min_rows)
        if self.metrics is not None:
            from repro.core import telemetry as tm
            self.metrics = tm.note_downdate(self.metrics,
                                            self.kpca_state.m)
        return self.state

    def update_block(self, xs: Array):
        """Scan over a block of points — one compilation, exact sequential
        semantics (the paper's per-point algorithm, amortized for TPU).
        Bucketed dispatch scans within a bucket and re-buckets at
        crossings, keeping the same sequential semantics.  A windowed
        stream routes through ``Engine.window_block``: growth points scan
        append-only, and once the window fills the evict+ingest pairs run
        as ONE scanned dispatch per block (fixed shape at m ≡ W) instead
        of the old per-point host-decided stepping."""
        return self._unbundle(self.engine.step_block(
            self._bundle(), xs, window=self.window,
            min_rows=self._min_rows))

    # sklearn-style spelling for streaming consumers: identical semantics.
    partial_fit_block = update_block

    # ---- self-healing (core/health.py) ------------------------------------
    def heal(self, *, level: str = "auto"):
        """Walk the heal ladder on the stream's state (polish → resync;
        ``health.HealthError`` escalates to restore-from-checkpoint).
        Clears the sticky probe flags so post-heal probes start clean."""
        rung_out: list = []
        self.state = self.engine.heal(self.state, level=level,
                                      rung_out=rung_out)
        if self.health is not None:
            self.health = self.health._replace(
                nonfinite=jnp.zeros((), jnp.int32),
                orth_err=jnp.zeros((), self.health.orth_err.dtype))
        if self.metrics is not None and rung_out:
            from repro.core import telemetry as tm
            self.metrics = tm.note_heal(self.metrics, rung_out[-1])
        return self.state

    def health_report(self) -> dict:
        """Host-side snapshot of the riding HealthState (one sync)."""
        if self.health is None:
            return {}
        h = self.health
        return {"orth_err": float(h.orth_err), "neg_frac": float(h.neg_frac),
                "nonfinite": int(h.nonfinite),
                "quarantined": int(h.quarantined),
                "rejected_last": int(h.rejected_last),
                "probes": int(h.probes), "spec_drift": float(h.spec_drift)}

    def is_healthy(self) -> bool:
        """Verdict of the last in-graph probe against the plan policy."""
        if self.health is None:
            return True
        from repro.core import health as hl
        return hl.is_healthy(self.health, self.plan.health)

    def metrics_report(self) -> dict:
        """Host snapshot of the riding MetricsState (one sync); empty
        without ``plan.metrics``."""
        if self.metrics is None:
            return {}
        from repro.core import telemetry as tm
        return tm.metrics_report(self.metrics)

    def truncate(self, k: int, *, compact: bool | None = None) -> KPCAState:
        """Keep only the k dominant eigenpairs (paper conclusion: 'adapt the
        proposed algorithm to only maintain a subset') — subsequent updates
        then track the dominant subspace at O(k³)-per-update cost, trading
        exactness for the Hoegaerts-style subset regime.

        With ``compact`` (default: ``plan.compact_shrink``) the state is
        re-expressed on its leading k rows and the arrays shrink to the
        active bucket; without it the old rows keep eigenvector support
        and bucketed dispatch keeps slicing at the old active count.
        That support floor is host-side stream state — it does NOT
        survive a checkpoint, so compact a truncated stream before
        saving it mid-stream.
        """
        if self.window is not None:
            raise ValueError("truncate is not supported on a windowed "
                             "stream — the window itself bounds the state")
        if compact is None:
            compact = self.plan.compact_shrink
        support = max(int(self.state.m), self._min_rows)
        self.state = self.engine.truncate(self.state, k, compact=compact)
        self._min_rows = 0 if compact else support
        return self.state

    # ---- read-out utilities -------------------------------------------------
    def eigpairs(self) -> tuple[Array, Array]:
        """Active (descending) eigenvalues and eigenvectors."""
        return eng.eigpairs(self.kpca_state)

    def reconstruction(self) -> Array:
        st = self.kpca_state
        return rankone.reconstruct(st.L, st.U, st.m)

    def transform(self, x: Array, n_components: int) -> Array:
        """Project new points on the leading kernel principal components.

        Under ``plan.fuse_krow`` the projection runs the fused
        query-gram+projection kernel; with bucketed dispatch the state is
        first sliced to the smallest bucket holding the active set (the
        slice is lossless — engine invariants), so the transform costs
        O(Q·m_b·(d+k)) instead of O(Q·M·(d+k)) at small active counts."""
        st = self.kpca_state
        if self.plan.fuse_krow and self.plan.dispatch == "bucketed":
            need = max(int(st.m), self._min_rows, n_components, 1)
            Mb = eng.bucket_for(need, st.L.shape[0], self.plan.min_bucket)
            if Mb < st.L.shape[0]:
                st = eng.slice_state(st, Mb)
        return eng.transform_state(st, x, spec=self.spec,
                                   adjusted=self.adjusted,
                                   n_components=n_components,
                                   plan=self.plan)
