"""Fused kernel-row ingest + fused batched transform (ISSUE 6).

The fused kernels must be numerically the reference pipeline:

* ``rbf_gram.krow_project`` (a, P) == masked kernel row + U^T [a | aux],
  square and rectangular row blocks, both stationary kernels, f32/f64,
  interpret mode exercising the real Pallas body with tile pruning.
* one fused ingest step == one unfused step (masked_row then update),
  adjusted and unadjusted, single- and double-rotation matmul modes.
* ``nystrom_recon.transform_project`` == the masked-gram projection, and
  ``engine.transform_state`` under a fused plan == the unfused path
  (including the adjusted centering post-correction and the bucketed
  slice the stream applies before transforming).
* the distributed window scan with ``fuse_krow`` (psum'd partial P,
  injected Z) == the local unfused stream on a real P=2 mesh.
* ``StreamBatch.update_block`` with a window and a mixed cohort (steady
  lanes scanned, growing lanes stepped) == the per-point update loop.
* the incremental swap/removal trace deltas keep ``TraceErrorTracker``
  on the exact ``trace_error`` over a replace-heavy landmark lifecycle.
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import engine as eng, inkpca, kernels_fn as kf, nystrom, \
    rankone
from repro.kernels.nystrom_recon import ops as nops
from repro.kernels.nystrom_recon.ref import transform_project_ref
from repro.kernels.nystrom_recon.transform_batch import (
    transform_project as transform_project_pallas)
from repro.kernels.rbf_gram import ops as gops
from repro.kernels.rbf_gram.krow_fused import krow_project as krow_pallas
from repro.kernels.rbf_gram.ref import krow_project_ref

SPECS = {"rbf": kf.KernelSpec(name="rbf", sigma=5.0),
         "matern32": kf.KernelSpec(name="matern32", sigma=2.0)}


def _tol(dtype):
    return 1e-5 if dtype == jnp.float32 else 1e-12


def _invariant_u(rng, M, m, dtype):
    """Capacity-M eigenvector matrix honoring the state invariant:
    inactive columns are exact identity columns, active columns have no
    mass on rows >= m (what tile pruning relies on)."""
    q, _ = np.linalg.qr(rng.normal(size=(m, m)))
    u = np.eye(M)
    u[:m, :m] = q
    return jnp.asarray(u, dtype)


def _grown_state(n, capacity, d, spec, *, adjusted, dtype, seed=0):
    """Grow an unfused fixed-dispatch state to n active points."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    st = inkpca.init_state(jnp.asarray(X[:4], dtype), capacity, spec,
                           adjusted=adjusted, dtype=dtype)
    plan = eng.UpdatePlan().kernel_plan()
    for i in range(4, n):
        st = eng._ingest(st, jnp.asarray(X[i], dtype), spec, adjusted, plan)
    return st


# ------------------------------------------------------ krow_project ----
@pytest.mark.parametrize("name", ["rbf", "matern32"])
def test_krow_project_ref_matches_manual(name):
    spec = SPECS[name]
    rng = np.random.default_rng(3)
    M, m, d = 24, 9, 5
    u = _invariant_u(rng, M, m, jnp.float64)
    x = jnp.asarray(rng.normal(size=(M, d)))
    x_new = jnp.asarray(rng.normal(size=(d,)))
    aux = jnp.asarray(rng.normal(size=(M, 2)))
    a, P = krow_project_ref(u, x, x_new, aux, jnp.int32(m), spec=spec)
    kr = kf.gram_block(x, x_new[None, :], spec=spec)[:, 0]
    a_man = jnp.where(jnp.arange(M) < m, kr, 0.0)
    aux_man = jnp.where(jnp.arange(M)[:, None] < m, aux, 0.0)
    P_man = u.T @ jnp.concatenate([a_man[:, None], aux_man], axis=1)
    np.testing.assert_allclose(a, a_man, atol=1e-14)
    np.testing.assert_allclose(P, P_man, atol=1e-14)


@pytest.mark.parametrize("name", ["rbf", "matern32"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_krow_project_interpret_matches_ref_square(name, dtype):
    """Real Pallas body (interpret) vs oracle, block=8 so the m=10 active
    prefix prunes row/col tiles inside the M=32 grid."""
    spec = SPECS[name]
    rng = np.random.default_rng(4)
    M, m, d = 32, 10, 6
    u = _invariant_u(rng, M, m, dtype)
    x = jnp.asarray(np.where(np.arange(M)[:, None] < m,
                             rng.normal(size=(M, d)), 0.0), dtype)
    x_new = jnp.asarray(rng.normal(size=(d,)), dtype)
    aux = jnp.asarray(rng.normal(size=(M, 2)), dtype)
    a_r, P_r = krow_project_ref(u, x, x_new, aux, jnp.int32(m), spec=spec)
    a_p, P_p = krow_pallas(u, x, x_new, aux, jnp.int32(m), spec=spec,
                           block=8, interpret=True)
    np.testing.assert_allclose(a_p, a_r, atol=_tol(dtype))
    np.testing.assert_allclose(P_p, P_r, atol=_tol(dtype))


@pytest.mark.parametrize("r0", [0, 16])
def test_krow_project_rectangular_row_block(r0):
    """(R, M) shard covering global rows [r0, r0+R): partial P sums over
    shards to the full projection (the distributed contract)."""
    spec = SPECS["rbf"]
    rng = np.random.default_rng(5)
    M, R, m, d = 32, 16, 10, 6
    dtype = jnp.float64
    u = _invariant_u(rng, M, m, dtype)
    x = jnp.asarray(np.where(np.arange(M)[:, None] < m,
                             rng.normal(size=(M, d)), 0.0), dtype)
    x_new = jnp.asarray(rng.normal(size=(d,)), dtype)
    aux = jnp.asarray(rng.normal(size=(M, 2)), dtype)
    sh = slice(r0, r0 + R)
    a_r, P_r = krow_project_ref(u[sh], x[sh], x_new, aux[sh], jnp.int32(m),
                                jnp.int32(r0), spec=spec)
    a_p, P_p = krow_pallas(u[sh], x[sh], x_new, aux[sh], jnp.int32(m),
                           jnp.int32(r0), spec=spec, block=8, interpret=True)
    np.testing.assert_allclose(a_p, a_r, atol=1e-12)
    np.testing.assert_allclose(P_p, P_r, atol=1e-12)
    # Both shards together reproduce the square projection.
    a_f, P_f = krow_project_ref(u, x, x_new, aux, jnp.int32(m), spec=spec)
    other = slice(16 - r0, 32 - r0)
    _, P_o = krow_pallas(u[other], x[other], x_new, aux[other], jnp.int32(m),
                         jnp.int32(16 - r0), spec=spec, block=8,
                         interpret=True)
    np.testing.assert_allclose(P_p + P_o, P_f, atol=1e-12)
    np.testing.assert_allclose(a_f[sh], a_p, atol=1e-12)


def test_krow_ops_dispatch_forces_ref_for_non_stationary():
    """Kernels without a fused epilogue (linear) must dispatch to the
    reference path even when a Pallas force is requested."""
    spec = kf.KernelSpec(name="linear", sigma=1.0)
    rng = np.random.default_rng(6)
    M, m, d = 16, 6, 4
    u = _invariant_u(rng, M, m, jnp.float64)
    x = jnp.asarray(rng.normal(size=(M, d)))
    x_new = jnp.asarray(rng.normal(size=(d,)))
    aux = jnp.zeros((M, 0))
    a_r, P_r = krow_project_ref(u, x, x_new, aux, jnp.int32(m), spec=spec)
    a_o, P_o = gops.krow_project(u, x, x_new, aux, jnp.int32(m), spec=spec,
                                 force="interpret")
    np.testing.assert_allclose(a_o, a_r, atol=1e-14)
    np.testing.assert_allclose(P_o, P_r, atol=1e-14)


# ------------------------------------------------------- fused ingest ----
@pytest.mark.parametrize("adjusted", [False, True])
@pytest.mark.parametrize("matmul", ["jnp", "jnp2"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_fused_ingest_matches_unfused_single_step(adjusted, matmul, dtype):
    spec = SPECS["rbf"]
    st = _grown_state(12, 32, 5, spec, adjusted=adjusted, dtype=dtype)
    x_new = jnp.asarray(np.random.default_rng(7).normal(size=(5,)), dtype)
    plan_u = eng.UpdatePlan(matmul=matmul).kernel_plan()
    plan_f = eng.UpdatePlan(matmul=matmul, fuse_krow=True).kernel_plan()
    s_u = eng._ingest(st, x_new, spec, adjusted, plan_u)
    s_f = eng._ingest(st, x_new, spec, adjusted, plan_f)
    tol = _tol(dtype)
    m = int(s_u.m)
    assert int(s_f.m) == m
    np.testing.assert_allclose(s_f.L[:m], s_u.L[:m], atol=tol, rtol=tol)
    K_u = rankone.reconstruct(s_u.L, s_u.U, s_u.m)
    K_f = rankone.reconstruct(s_f.L, s_f.U, s_f.m)
    np.testing.assert_allclose(K_f, K_u, atol=10 * tol)
    np.testing.assert_allclose(s_f.X, s_u.X, atol=tol)
    if adjusted:
        np.testing.assert_allclose(s_f.K1, s_u.K1, atol=tol)
        np.testing.assert_allclose(s_f.S, s_u.S, atol=tol)


@pytest.mark.parametrize("name", ["rbf", "matern32"])
def test_fused_bucketed_stream_matches_fixed_unfused(name):
    """End-to-end KPCAStream: fused + bucketed + double-rotation vs the
    seed fixed unfused path over a 20-point stream (accumulated fp drift
    bounded, not bitwise)."""
    spec = SPECS[name]
    rng = np.random.default_rng(8)
    X = rng.normal(size=(20, 5))
    kw = dict(adjusted=True, dtype=jnp.float64)
    s_ref = inkpca.KPCAStream(jnp.asarray(X[:4]), 64, spec,
                              plan=eng.UpdatePlan(dispatch="fixed"), **kw)
    s_fus = inkpca.KPCAStream(
        jnp.asarray(X[:4]), 64, spec,
        plan=eng.UpdatePlan(matmul="jnp2", dispatch="bucketed",
                            fuse_krow=True), **kw)
    for i in range(4, 20):
        s_ref.update(jnp.asarray(X[i]))
        s_fus.update(jnp.asarray(X[i]))
    a, b = s_ref.kpca_state, s_fus.kpca_state
    assert int(a.m) == int(b.m) == 20
    K_a = rankone.reconstruct(a.L, a.U, a.m)
    K_b = rankone.reconstruct(b.L, b.U, b.m)
    np.testing.assert_allclose(K_b, K_a, atol=1e-8)
    q = jnp.asarray(rng.normal(size=(3, 5)))
    np.testing.assert_allclose(s_fus.transform(q, n_components=6),
                               s_ref.transform(q, n_components=6), atol=1e-7)


# --------------------------------------------------- fused transform ----
@pytest.mark.parametrize("name", ["rbf", "matern32"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("Q", [32, 50])
def test_transform_project_interpret_matches_ref(name, dtype, Q):
    spec = SPECS[name]
    rng = np.random.default_rng(9)
    M, d, C, m = 32, 6, 4, 11
    x = jnp.asarray(rng.normal(size=(M, d)), dtype)
    xq = jnp.asarray(rng.normal(size=(Q, d)), dtype)
    s = jnp.asarray(rng.normal(size=(M, C)), dtype)
    y_r, rs_r = transform_project_ref(xq, x, s, jnp.int32(m), spec=spec)
    y_p, rs_p = transform_project_pallas(xq, x, s, jnp.int32(m), spec=spec,
                                         block=8, interpret=True)
    tol = _tol(dtype) * 10
    np.testing.assert_allclose(y_p, y_r, atol=tol)
    np.testing.assert_allclose(rs_p, rs_r, atol=tol)


@pytest.mark.parametrize("adjusted", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_transform_state_fused_matches_unfused(adjusted, dtype):
    spec = SPECS["rbf"]
    st = _grown_state(14, 32, 5, spec, adjusted=adjusted, dtype=dtype)
    q = jnp.asarray(np.random.default_rng(10).normal(size=(7, 5)), dtype)
    plan = eng.UpdatePlan(fuse_krow=True).kernel_plan()
    y_u = eng.transform_state(st, q, spec=spec, adjusted=adjusted,
                              n_components=6, plan=None)
    y_f = eng.transform_state(st, q, spec=spec, adjusted=adjusted,
                              n_components=6, plan=plan)
    np.testing.assert_allclose(y_f, y_u, atol=_tol(dtype) * 10)
    # Bucketed spelling: transforming the sliced state is the same map.
    Mb = eng.bucket_for(int(st.m), 32, plan.min_bucket)
    if Mb < 32:
        y_b = eng.transform_state(eng.slice_state(st, Mb), q, spec=spec,
                                  adjusted=adjusted, n_components=6,
                                  plan=plan)
        np.testing.assert_allclose(y_b, y_u, atol=_tol(dtype) * 10)


def test_stream_transform_routes_fused_bucketed():
    """KPCAStream.transform under a fused bucketed plan slices to the
    active bucket before the fused projection — output must match the
    full-capacity unfused transform."""
    spec = SPECS["rbf"]
    rng = np.random.default_rng(11)
    X = rng.normal(size=(12, 5))
    stream = inkpca.KPCAStream(
        jnp.asarray(X[:4]), 64, spec, adjusted=True,
        plan=eng.UpdatePlan(dispatch="bucketed", fuse_krow=True),
        dtype=jnp.float64)
    for i in range(4, 12):
        stream.update(jnp.asarray(X[i]))
    q = jnp.asarray(rng.normal(size=(5, 5)))
    y_f = stream.transform(q, n_components=4)
    y_u = eng.transform_state(stream.kpca_state, q, spec=spec, adjusted=True,
                              n_components=4, plan=None)
    np.testing.assert_allclose(y_f, y_u, atol=1e-11)


def test_nystrom_fused_add_landmark_and_query_features():
    spec = SPECS["rbf"]
    rng = np.random.default_rng(12)
    x0 = jnp.asarray(rng.normal(size=(4, 5)))
    # f64 lifecycle: per-step fused-vs-unfused is exact, but f32 rounding
    # differences compound through near-degenerate secular solves when the
    # two states evolve independently for several steps.
    state = nystrom.init_nystrom(None, x0, 16, spec, grow_rows=True,
                                 dtype=jnp.float64)
    plan_u = eng.UpdatePlan().kernel_plan()
    plan_f = eng.UpdatePlan(fuse_krow=True).kernel_plan()
    s_u = s_f = state
    for i in range(6):
        x = jnp.asarray(rng.normal(size=(5,)))
        s_u = nystrom.observe_rows(s_u, x, spec, plan=plan_u)
        s_f = nystrom.observe_rows(s_f, x, spec, plan=plan_f)
        s_u = nystrom.add_landmark(s_u, None, x, spec, plan=plan_u)
        s_f = nystrom.add_landmark(s_f, None, x, spec, plan=plan_f)
    K_u = rankone.reconstruct(s_u.kpca.L, s_u.kpca.U, s_u.kpca.m)
    K_f = rankone.reconstruct(s_f.kpca.L, s_f.kpca.U, s_f.kpca.m)
    np.testing.assert_allclose(K_f, K_u, atol=1e-10)
    np.testing.assert_allclose(s_f.Knm, s_u.Knm, atol=1e-12)
    xq = jnp.asarray(rng.normal(size=(4, 5)))
    f_u = nystrom.query_features(s_u, xq, 3, spec, plan=plan_u)
    f_f = nystrom.query_features(s_f, xq, 3, spec, plan=plan_f)
    np.testing.assert_allclose(f_f, f_u, atol=1e-10)


# ----------------------------------------- distributed fused window ----
def test_sharded_fused_window_multidevice_subprocess():
    """P=2 end-to-end: the sharded window block under ``fuse_krow`` (per
    shard partial P psum'd into the injected Z) must match the local
    unfused stream."""
    script = r"""
import numpy as np, jax, jax.numpy as jnp
jax.config.update("jax_enable_x64", True)
from repro.core import distributed as dkpca, engine as eng, inkpca, \
    kernels_fn as kf, rankone
assert jax.device_count() == 2
SPEC = kf.KernelSpec(name="rbf", sigma=5.0)
rng = np.random.default_rng(21)
X = rng.normal(size=(12, 4))
W = 8
stream = inkpca.KPCAStream(jnp.asarray(X[:4]), 16, SPEC, adjusted=False,
                           dtype=jnp.float64, window=W)
for i in range(4, 12):
    stream.update(jnp.asarray(X[i]))
ws = stream.state
xs = jnp.asarray(rng.normal(size=(5, 4)))
from repro.distributed.sharding import make_mesh
mesh = make_mesh((2,), ("data",))
errs = {}
for tag, plan in (("fixed", eng.UpdatePlan(fuse_krow=True, matmul="jnp2")),
                  ("bucketed", eng.UpdatePlan(dispatch="bucketed",
                                              min_bucket=8, fuse_krow=True,
                                              matmul="jnp2"))):
    wb = dkpca.make_sharded_window_block(mesh, SPEC, plan=plan)
    L2, U2, X2, ages2, clock2 = wb(ws.kpca.L, ws.kpca.U, ws.kpca.X,
                                   ws.ages, ws.clock, xs, ws.kpca.m)
    ref = stream
    import copy
    ref = inkpca.KPCAStream(jnp.asarray(X[:4]), 16, SPEC, adjusted=False,
                            dtype=jnp.float64, window=W)
    for i in range(4, 12):
        ref.update(jnp.asarray(X[i]))
    for t in range(5):
        ref.update(xs[t])
    r = ref.state
    errs[tag + "_L"] = float(jnp.abs(L2[:W] - r.kpca.L[:W]).max())
    errs[tag + "_K"] = float(jnp.abs(
        rankone.reconstruct(L2, U2, jnp.int32(W))
        - rankone.reconstruct(r.kpca.L, r.kpca.U, r.kpca.m)).max())
print("RESULT:" + str(errs))
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2")
    env["PYTHONPATH"] = (str(Path(__file__).resolve().parent.parent / "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT:")][-1]
    errs = eval(line[len("RESULT:"):])
    for k, v in errs.items():
        assert v < 1e-9, errs


# ------------------------------------- StreamBatch windowed blocks ----
@pytest.mark.parametrize("cohorts", ["max", "bucket"])
def test_streambatch_windowed_block_matches_per_point(cohorts):
    """Mixed cohort at a window: steady lanes fold the block in one scan,
    growers step to the window then scan — must equal the per-point loop."""
    spec = SPECS["rbf"]
    rng = np.random.default_rng(13)
    B, d, W, cap = 3, 4, 6, 16
    x0 = jnp.asarray(rng.normal(size=(B, 4, d)))
    kw = dict(plan=eng.UpdatePlan(dispatch="bucketed", min_bucket=8),
              adjusted=True, dtype=jnp.float64, cohorts=cohorts, window=W)
    blk = eng.StreamBatch(x0, cap, spec, **kw)
    ref = eng.StreamBatch(x0, cap, spec, **kw)
    # Stagger: tenant 0 reaches the window first via masked updates.
    pre = jnp.asarray(rng.normal(size=(2, B, d)))
    mask = jnp.asarray([True, False, False])
    for t in range(2):
        blk.update(pre[t], active=mask)
        ref.update(pre[t], active=mask)
    assert list(blk._m_host) == [6, 4, 4]
    xs = jnp.asarray(rng.normal(size=(5, B, d)))
    blk.update_block(xs)
    for t in range(5):
        ref.update(xs[t])
    sa, sb = blk.states, ref.states
    assert list(blk._m_host) == list(ref._m_host)
    for la, lb in zip(jax.tree_util.tree_leaves(sa),
                      jax.tree_util.tree_leaves(sb)):
        np.testing.assert_allclose(la, lb, atol=1e-9)


# ---------------------------------------------- trace-delta tracking ----
def test_removal_trace_delta_matches_exact():
    spec = SPECS["rbf"]
    rng = np.random.default_rng(14)
    x0 = jnp.asarray(rng.normal(size=(4, 4)))
    state = nystrom.init_nystrom(None, x0, 16, spec, grow_rows=True)
    for i in range(8):
        x = jnp.asarray(rng.normal(size=(4,)))
        state = nystrom.observe_rows(state, x, spec)
        if i % 2 == 0:
            state = nystrom.add_landmark(state, None, x, spec)
    before = float(nystrom.trace_error(state, spec))
    for j in [0, 3, 6]:
        delta, wjj = nystrom.removal_trace_delta(state, jnp.int32(j))
        assert float(wjj) > 0
        after = float(nystrom.trace_error(
            nystrom.remove_landmark(state, jnp.int32(j), spec), spec))
        np.testing.assert_allclose(after - before, float(delta), atol=1e-9)


def test_tracker_swap_delta_drift_over_replace_heavy_lifecycle():
    """Replace-heavy landmark lifecycle: the tracker (swap deltas, no
    periodic resync) must stay on the exact trace_error.

    The leverage policy's swap arm compares ridge-leverage scores that
    saturate near 1 for any non-degenerate landmark set against a
    normalized residual below 1, so an i.i.d. candidate stream never
    fires it on its own; the swap-heavy lifecycle is driven explicitly
    through ``Engine.replace_landmark`` with the policy's own
    argmin-leverage victim choice, which is what exercises the
    ``swap_trace_delta`` path this test is about."""
    spec = SPECS["rbf"]
    rng = np.random.default_rng(15)
    x0 = jnp.asarray(rng.normal(size=(4, 4)))
    state = nystrom.init_nystrom(None, x0, 16, spec, grow_rows=True)
    engine = eng.Engine(spec, eng.UpdatePlan(landmark_policy="leverage"),
                        adjusted=False)
    tracker = nystrom.TraceErrorTracker(state, spec, resync_every=10_000)
    counts = {"admitted": 0, "rejected": 0, "replaced": 0}
    for i in range(36):
        x = jnp.asarray(rng.normal(size=(4,)))
        res = float(nystrom.admission_residual(state, x, spec))
        tracker.observe(state, x, residual=res)
        state = nystrom.observe_rows(state, x, spec)
        prev = state
        m = int(state.kpca.m)
        if m >= 6 and i % 3 == 0:
            lev = np.asarray(nystrom.leverage_scores(state)[:m])
            victim = int(np.argmin(lev))
            state = engine.replace_landmark(state, None, victim, x)
            action = "replaced"
        else:
            state, action = engine.offer_landmark(state, x, budget=6,
                                                  residual=res)
        counts[action] += 1
        if action == "admitted":
            tracker.admitted(prev, x)
        elif action == "replaced":
            tracker.replaced(state, state_before=prev, x=x)
    assert counts["replaced"] >= 5, counts    # lifecycle must be swap-heavy
    exact = float(nystrom.trace_error(state, spec))
    # ~1e-8 relative rounding per accumulated swap delta, 11 swaps here
    assert abs(tracker.value - exact) <= 1e-7 * max(exact, 1.0), \
        (tracker.value, exact, counts)
