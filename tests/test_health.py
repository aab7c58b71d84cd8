"""Self-healing layer (``core/health``): probes, quarantine, heal ladder,
graceful serving degradation and staleness-aware publication.

The quarantine tests assert BITWISE equality between a stream that saw a
poisoned point and one that never did — the gate must reject before the
rank-one pair fires, leaving the eigensystem, arrival ring and clock
untouched on every dispatch path (fixed, bucketed, scanned window,
multi-tenant).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import batch as batch_mod
from repro.core import engine as eng
from repro.core import health as hl
from repro.core import inkpca
from repro.core import kernels_fn as kf
from repro.core import serving
from repro.testing import faults

SPEC = kf.KernelSpec(name="rbf", sigma=2.0)
HPLAN = eng.UpdatePlan(health=hl.DEFAULT_POLICY)


def _stream(n=10, d=4, cap=16, *, plan=eng.UpdatePlan(), adjusted=True,
            dtype=jnp.float64, seed=0, window=None):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    s = inkpca.KPCAStream(jnp.asarray(X[:4], dtype), cap, SPEC,
                          adjusted=adjusted, plan=plan, dtype=dtype,
                          window=window)
    for i in range(4, n):
        s.update(jnp.asarray(X[i], dtype))
    return s, rng


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ------------------------------------------------------------- probes --
def test_probe_healthy_then_detects_corruption():
    s, _ = _stream(12)
    st = s.kpca_state
    h = hl.probe(st, hl.init_health(st.L.dtype), hl.DEFAULT_POLICY)
    assert hl.is_healthy(h, hl.DEFAULT_POLICY)
    assert float(h.orth_err) < 1e-8

    bad = faults.corrupt_eigvecs(st, magnitude=0.3, seed=1)
    h2 = hl.probe(bad, hl.init_health(st.L.dtype), hl.DEFAULT_POLICY)
    assert not hl.is_healthy(h2, hl.DEFAULT_POLICY)
    assert float(h2.orth_err) > 1e-2

    neg = faults.corrupt_eigenvalue(st, j=0, value=-1.0)
    h3 = hl.probe(neg, hl.init_health(st.L.dtype), hl.DEFAULT_POLICY)
    assert not hl.is_healthy(h3, hl.DEFAULT_POLICY)
    assert float(h3.neg_frac) > hl.DEFAULT_POLICY.neg_tol

    nanU = st._replace(U=st.U.at[0, 0].set(jnp.nan))
    h4 = hl.probe(nanU, hl.init_health(st.L.dtype), hl.DEFAULT_POLICY)
    assert int(h4.nonfinite) == 1
    # sticky: a later healthy probe does not clear the flag
    h5 = hl.probe(st, h4, hl.DEFAULT_POLICY)
    assert int(h5.nonfinite) == 1


def test_probe_rotates_over_all_columns():
    s, _ = _stream(12)
    st = s.kpca_state
    # A support violation in any one column is caught by the first probe:
    # the probe covers every column at once.
    for j in range(int(st.m)):
        bad = st._replace(U=st.U.at[int(st.m) - 1, j].add(0.5))
        h = hl.probe(bad, hl.init_health(st.L.dtype), hl.DEFAULT_POLICY)
        assert float(h.orth_err) > 1e-2, j


# --------------------------------------------------------- quarantine --
@pytest.mark.parametrize("plan", [
    eng.UpdatePlan(health=hl.DEFAULT_POLICY),
    eng.UpdatePlan(dispatch="bucketed", min_bucket=8,
                   health=hl.DEFAULT_POLICY),
], ids=["fixed", "bucketed"])
def test_guarded_update_bitwise_reject(plan):
    engine = eng.Engine(SPEC, plan, adjusted=True)
    ref_engine = eng.Engine(SPEC, plan._replace(health=None), adjusted=True)
    s, rng = _stream(9)
    st = s.kpca_state
    h = hl.init_health(st.L.dtype)

    # clean point: guarded == unguarded, bit for bit
    x = jnp.asarray(rng.normal(size=(4,)))
    st1, h1 = engine.update_guarded(st, h, x)
    _assert_trees_equal(st1, ref_engine.update(st, x))
    assert int(h1.quarantined) == 0 and int(h1.rejected_last) == 0

    # poisoned point: state survives bitwise, counter ticks
    st2, h2 = st1, h1
    for kind in ("nan", "inf", "-inf"):
        st2, h2 = engine.update_guarded(st2, h2, faults.nan_point(
            4, kind=kind, base=np.asarray(x)))
        _assert_trees_equal(st2, st1)
    assert int(h2.quarantined) == 3 and int(h2.rejected_last) == 1


def test_guarded_block_splits_at_poisoned_points():
    plan = eng.UpdatePlan(health=hl.DEFAULT_POLICY)
    engine = eng.Engine(SPEC, plan, adjusted=True)
    s, rng = _stream(8)
    st = s.kpca_state
    xs = rng.normal(size=(6, 4))
    bad = np.array(xs)
    bad[2] = faults.nan_point(4, base=bad[2])
    clean = np.delete(np.array(xs), 2, axis=0)

    h = hl.init_health(st.L.dtype)
    got, hg = engine.update_block_guarded(st, h, jnp.asarray(bad))
    ref, _ = engine.update_block_guarded(st, hl.init_health(st.L.dtype),
                                         jnp.asarray(clean))
    _assert_trees_equal(got, ref)
    assert int(hg.quarantined) == 1


def test_window_ingest_quarantine_leaves_ring_untouched():
    """The PR's window bugfix: a rejected point must leave the kpca state,
    the ages ring AND the clock exactly as they were — the old path
    evicted and stamped regardless."""
    W = 6
    plan = eng.UpdatePlan(window=W, health=hl.DEFAULT_POLICY)
    engine = eng.Engine(SPEC, plan, adjusted=True)
    s, rng = _stream(10, window=W, plan=plan)
    ws = s.state

    from repro.core import window as win
    out, h = win.ingest(engine, ws, faults.nan_point(4), window=W,
                        hstate=hl.init_health(ws.kpca.L.dtype))
    _assert_trees_equal(out, ws)
    assert int(h.quarantined) == 1

    # and the stream-level spelling: poisoned mid-stream == never seen
    p2 = eng.UpdatePlan(window=W, health=hl.DEFAULT_POLICY)
    sa, rng = _stream(10, window=W, plan=p2, seed=3)
    sb, _ = _stream(10, window=W, plan=p2, seed=3)
    xs = rng.normal(size=(4, 4))
    for t in range(4):
        sa.update(jnp.asarray(xs[t]))
        sb.update(jnp.asarray(xs[t]))
        if t == 1:
            sa.update(faults.nan_point(4))
    _assert_trees_equal(sa.state, sb.state)
    assert int(sa.health.quarantined) == 1
    assert int(sb.health.quarantined) == 0


@pytest.mark.parametrize("cohorts,window", [("max", None), ("max", 6),
                                            ("bucket", None),
                                            ("bucket-padded", 6)])
def test_streambatch_quarantine_bitwise(cohorts, window):
    rng = np.random.default_rng(0)
    B, d, cap = 3, 4, 16
    x0 = rng.normal(size=(B, 4, d))
    plan = eng.UpdatePlan(health=hl.DEFAULT_POLICY)
    sb = eng.StreamBatch(jnp.asarray(x0), cap, SPEC, plan=plan,
                         dtype=jnp.float64, cohorts=cohorts, window=window)
    rf = eng.StreamBatch(jnp.asarray(x0), cap, SPEC, plan=eng.UpdatePlan(),
                         dtype=jnp.float64, cohorts=cohorts, window=window)
    T = 8
    xs = rng.normal(size=(T, B, d))
    bad = np.array(xs)
    bad[3, 1, 0] = np.nan
    bad[6, 0, 2] = np.inf
    sb.update_block(jnp.asarray(bad))
    # Reference mirrors the guarded dispatch split: clean runs ride the
    # block path, poisoned steps the per-step masked path — bitwise
    # equality then isolates the quarantine gate itself.
    finite = np.isfinite(bad).all(axis=(1, 2))
    t = 0
    while t < T:
        if finite[t]:
            u = t
            while u < T and finite[u]:
                u += 1
            rf.update_block(jnp.asarray(bad[t:u]))
            t = u
        else:
            ok = np.isfinite(bad[t]).all(axis=1)
            rf.update(jnp.asarray(np.where(ok[:, None], bad[t], 0.0)),
                      active=ok)
            t += 1
    _assert_trees_equal(sb.states, rf.states)
    assert sb.health_summary()["quarantined"] == 2
    np.testing.assert_array_equal(sb.quarantined, [1, 1, 0])
    np.testing.assert_array_equal(sb._m_host, rf._m_host)


def test_outlier_gate_rejects_far_point():
    # RBF: a point far outside the stored set has k(x,x) = 1 but a kernel
    # row that underflows to ~0 — with outlier_tol on, it is quarantined.
    spec = kf.KernelSpec(name="rbf", sigma=0.5)
    pol = hl.HealthPolicy(outlier_tol=1e-6)
    engine = eng.Engine(spec, eng.UpdatePlan(health=pol), adjusted=False)
    rng = np.random.default_rng(5)
    st = inkpca.init_state(jnp.asarray(rng.normal(size=(5, 3))), 8, spec,
                           adjusted=False, dtype=jnp.float64)
    h = hl.init_health(st.L.dtype)
    far = jnp.full((3,), 1e3, jnp.float64)
    st1, h1 = engine.update_guarded(st, h, far)
    _assert_trees_equal(st1, st)
    assert int(h1.quarantined) == 1
    # a nearby point still passes
    st2, h2 = engine.update_guarded(st1, h1,
                                    jnp.asarray(rng.normal(size=(3,))))
    assert int(st2.m) == int(st.m) + 1
    assert int(h2.quarantined) == 1


# -------------------------------------------------------- heal ladder --
def test_heal_polish_restores_orthogonality():
    s, _ = _stream(12)
    st = s.kpca_state
    tilted = faults.corrupt_eigvecs(st, magnitude=1e-3, seed=7)
    r0 = hl.exact_orth_residual(tilted)
    # unhealthy, but inside the polish band (orth_tol, polish_max)
    assert hl.DEFAULT_POLICY.orth_tol < r0 < hl.DEFAULT_POLICY.polish_max
    healed = hl.heal_kpca(tilted, SPEC, True)
    assert hl.exact_orth_residual(healed) < 1e-10


def test_heal_resync_matches_batch_kpca_f32():
    """Post-heal the state must match a from-scratch batch KPCA of the
    stored points to f32 round-off (acceptance: <= 1e-6)."""
    s, _ = _stream(12, dtype=jnp.float32)
    st = s.kpca_state
    bad = faults.corrupt_eigvecs(st, magnitude=0.5, seed=2)
    healed = hl.heal_kpca(bad, SPEC, True)   # auto escalates to resync
    m = int(st.m)
    K = kf.gram_block(st.X[:m], st.X[:m], spec=SPEC)
    lam, _ = batch_mod.batch_kpca(K, adjusted=True)
    np.testing.assert_allclose(np.sort(np.asarray(healed.L[:m])),
                               np.asarray(lam), atol=1e-6)
    assert hl.exact_orth_residual(healed) < 1e-5
    # the re-fit oracle lands on the same eigensystem
    refit = batch_mod.refit_state(st, SPEC, adjusted=True)
    np.testing.assert_allclose(np.asarray(healed.L), np.asarray(refit.L),
                               atol=1e-6)


def test_heal_noop_when_healthy_and_restore_rung():
    s, _ = _stream(10)
    st = s.kpca_state
    assert hl.heal_kpca(st, SPEC, True) is st   # auto: no-op
    poisoned = faults.poison_stored_row(st, row=1)
    with pytest.raises(hl.HealthError):
        hl.heal_kpca(poisoned, SPEC, True)
    with pytest.raises(hl.HealthError):
        hl.resync(poisoned, SPEC, True)


def test_engine_heal_routes_state_kinds():
    plan = eng.UpdatePlan(health=hl.DEFAULT_POLICY)
    engine = eng.Engine(SPEC, plan, adjusted=True)

    # plain KPCAState
    s, _ = _stream(10)
    bad = faults.corrupt_eigvecs(s.kpca_state, magnitude=0.5, seed=3)
    healed = engine.heal(bad)
    assert hl.exact_orth_residual(healed) < 1e-8

    # WindowState: ages/clock survive the heal
    W = 6
    wplan = eng.UpdatePlan(window=W, health=hl.DEFAULT_POLICY)
    sw, _ = _stream(10, window=W, plan=wplan)
    ws = sw.state
    wbad = ws._replace(kpca=faults.corrupt_eigvecs(ws.kpca, magnitude=0.5,
                                                   seed=4))
    wh = eng.Engine(SPEC, wplan, adjusted=True).heal(wbad)
    np.testing.assert_array_equal(np.asarray(wh.ages), np.asarray(ws.ages))
    assert int(wh.clock) == int(ws.clock)
    assert hl.exact_orth_residual(wh.kpca) < 1e-8


def test_stream_heal_after_drift_matches_batch():
    """Drift past threshold triggers heal; post-heal == batch KPCA."""
    plan = eng.UpdatePlan(health=hl.DEFAULT_POLICY)
    s, _ = _stream(12, plan=plan, dtype=jnp.float32)
    # inject drift directly into the stream state
    s.state = faults.corrupt_eigvecs(s.state, magnitude=0.3, seed=9)
    s.health = hl.probe(s.state, s.health, plan.health)
    assert not s.is_healthy()
    s.heal()
    s.health = hl.probe(s.state, s.health, plan.health)
    assert s.is_healthy()
    st = s.kpca_state
    m = int(st.m)
    K = kf.gram_block(st.X[:m], st.X[:m], spec=SPEC)
    lam, _ = batch_mod.batch_kpca(K, adjusted=True)
    np.testing.assert_allclose(np.sort(np.asarray(st.L[:m])),
                               np.asarray(lam), atol=1e-6)


# ------------------------------------------- serving degradation ------
def test_double_buffer_never_publishes_unhealthy():
    s, rng = _stream(10)
    buf = serving.DoubleBuffer(n_components=4, adjusted=True)
    with pytest.raises(ValueError):
        buf.publish(s.kpca_state, healthy=False)   # nothing to fall back on
    snap0 = buf.publish(s.kpca_state)
    gen0 = int(snap0.generation)

    s.update(jnp.asarray(rng.normal(size=(4,))))
    snap1 = buf.publish(s.kpca_state, healthy=False)
    assert snap1 is snap0
    assert buf.skipped == 1
    assert int(buf.front.generation) == gen0
    # queries still served from the stale-but-correct front
    y = buf.query(jnp.asarray(rng.normal(size=(3, 4))), spec=SPEC)
    assert np.isfinite(np.asarray(y)).all()

    snap2 = buf.publish(s.kpca_state, healthy=True)
    assert int(snap2.generation) == gen0 + 1
    assert buf.ref_lam is not None and buf.ref_lam.shape == (4,)


def test_ingest_serve_loop_serves_stale_under_faults():
    from repro.launch.serve import IngestServeLoop

    rng = np.random.default_rng(0)
    B, d, cap = 2, 4, 16
    plan = eng.UpdatePlan(serve_every=1, serve_components=4,
                          health=hl.DEFAULT_POLICY)
    batch = eng.StreamBatch(jnp.asarray(rng.normal(size=(B, 4, d))), cap,
                            SPEC, plan=plan, dtype=jnp.float64)
    loop = IngestServeLoop(batch, SPEC, n_components=4)
    loop.ingest(jnp.asarray(rng.normal(size=(B, d))))
    gen = loop.generation
    snap = loop.snaps

    # corrupt tenant 0 beyond repair: U *and* stored rows poisoned, so the
    # heal ladder ends in HealthError and publication must be refused
    batch._flush()
    full = batch._full
    U = np.array(full.U)
    U[0, :, 0] = np.nan
    X = np.array(full.X)
    X[0, 0] = np.nan
    batch._full = full._replace(U=jnp.asarray(U), X=jnp.asarray(X))

    published = loop.ingest(jnp.asarray(rng.normal(size=(B, d))))
    assert not published
    assert loop.skipped == 1
    assert loop.generation == gen
    assert loop.snaps is snap    # same object: the last healthy snapshot
    y = loop.query(jnp.asarray(rng.normal(size=(B, 3, d))))
    assert np.isfinite(np.asarray(y)).all()


def test_ingest_serve_loop_heals_and_publishes():
    from repro.launch.serve import IngestServeLoop

    rng = np.random.default_rng(1)
    B, d, cap = 2, 4, 16
    plan = eng.UpdatePlan(serve_every=1, serve_components=4,
                          health=hl.DEFAULT_POLICY)
    batch = eng.StreamBatch(jnp.asarray(rng.normal(size=(B, 4, d))), cap,
                            SPEC, plan=plan, dtype=jnp.float64)
    loop = IngestServeLoop(batch, SPEC, n_components=4)
    gen = loop.generation

    # recoverable corruption (stored rows intact): heal, then publish
    batch._flush()
    full = batch._full
    U = np.array(full.U)
    U[1, :3, :3] += 0.4
    batch._full = full._replace(U=jnp.asarray(U))

    assert loop.ingest(jnp.asarray(rng.normal(size=(B, d))))
    assert loop.heals >= 1
    assert loop.skipped == 0
    assert loop.generation == gen + 1


def test_staleness_aware_publication():
    from repro.launch.serve import IngestServeLoop

    rng = np.random.default_rng(2)
    B, d, cap = 2, 4, 32
    plan = eng.UpdatePlan(serve_every=1000, serve_components=4,
                          health=hl.DEFAULT_POLICY)
    batch = eng.StreamBatch(jnp.asarray(rng.normal(size=(B, 4, d))), cap,
                            SPEC, plan=plan, dtype=jnp.float64)
    loop = IngestServeLoop(batch, SPEC, n_components=4,
                           publish_on_drift=0.05)
    gen = loop.generation
    published = 0
    for t in range(12):
        # growing spectrum: drift accumulates until the trigger fires
        published += int(loop.ingest(jnp.asarray(
            rng.normal(size=(B, d)) * (1.0 + 0.5 * t))))
    assert loop.drift_publishes >= 1
    assert published == loop.drift_publishes   # cadence (1000) never fired
    assert loop.generation > gen


# ------------------------------------------------------------- soak ---
def test_soak_f32_periodic_heal_bounds_residual():
    """5k-step f32 sliding-window soak: with periodic healing the exact
    orthogonality residual stays under the policy threshold; with healing
    off the same stream drifts measurably past the healed run."""
    W, cap, d = 24, 32, 4
    rng = np.random.default_rng(0)
    plan = eng.UpdatePlan(window=W)
    engine = eng.Engine(SPEC, plan, adjusted=True)
    hengine = eng.Engine(SPEC, plan._replace(health=hl.DEFAULT_POLICY),
                         adjusted=True)

    from repro.core import window as win
    x0 = jnp.asarray(rng.normal(size=(4, d)), jnp.float32)
    ws_off = win.init_window(x0, cap, SPEC, adjusted=True,
                             dtype=jnp.float32)
    ws_on = ws_off

    steps, chunk = 5000, 500
    for c in range(steps // chunk):
        xs = jnp.asarray(rng.normal(size=(chunk, d)), jnp.float32)
        ws_off = engine.window_block(ws_off, xs, window=W)
        ws_on = engine.window_block(ws_on, xs, window=W)
        ws_on = hengine.heal(ws_on)
    r_off = hl.exact_orth_residual(ws_off.kpca)
    r_on = hl.exact_orth_residual(ws_on.kpca)
    assert np.isfinite(r_on) and np.isfinite(r_off)
    assert r_on <= hl.DEFAULT_POLICY.orth_tol, (r_on, r_off)
    assert r_on <= r_off, (r_on, r_off)


def test_checkpoint_restore_continue_after_corruption(tmp_path):
    """Restore rung end-to-end: corrupt stored rows -> heal raises ->
    reload last checkpoint, replay the tail -> equals the uninterrupted
    stream."""
    from repro.checkpoint import latest_step, load_checkpoint, \
        save_checkpoint

    d = str(tmp_path)
    plan = eng.UpdatePlan(health=hl.DEFAULT_POLICY)
    engine = eng.Engine(SPEC, plan, adjusted=True)
    s, rng = _stream(10, plan=plan)
    st = s.kpca_state
    save_checkpoint(d, 0, st._asdict())

    tail = rng.normal(size=(5, 4))
    ref = st
    h = hl.init_health(st.L.dtype)
    for t in range(5):
        ref, h = engine.update_guarded(ref, h, jnp.asarray(tail[t]))

    # corruption strikes the live state: the ladder ends in HealthError
    dead = faults.poison_stored_row(st, row=0)
    with pytest.raises(hl.HealthError):
        engine.heal(dead, level="resync")

    step = latest_step(d)
    restored = type(st)(**load_checkpoint(
        d, step, jax.eval_shape(lambda: st._asdict())))
    h2 = hl.init_health(st.L.dtype)
    got = restored
    for t in range(5):
        got, h2 = engine.update_guarded(got, h2, jnp.asarray(tail[t]))
    _assert_trees_equal(got, ref)
