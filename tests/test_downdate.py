"""Decremental updates (core/downdate.py): the inverse ±sigma pair +
contraction must exactly undo Algorithms 1/2, preserve every padding
invariant, and re-bucket downward under bucketed dispatch."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import engine as eng, inkpca, kernels_fn as kf, rankone
from repro.distributed.sharding import make_mesh

RNG = np.random.default_rng(11)
SPEC = kf.KernelSpec(name="rbf", sigma=5.0)


def _grow(adjusted, plan, n=11, capacity=16, d=4, seed_rng=None):
    rng = seed_rng if seed_rng is not None else RNG
    X = rng.normal(size=(n, d))
    engine = eng.Engine(SPEC, plan, adjusted=adjusted)
    st = inkpca.init_state(jnp.asarray(X[:4]), capacity, SPEC,
                          adjusted=adjusted, dtype=jnp.float64)
    for i in range(4, n):
        st = engine.update(st, jnp.asarray(X[i]))
    return engine, st, X


PLANS = [
    eng.UpdatePlan(),
    eng.UpdatePlan(dispatch="bucketed", min_bucket=8),
    eng.UpdatePlan(matmul="jnp2"),
    eng.UpdatePlan(dispatch="bucketed", min_bucket=8, matmul="jnp2"),
]


@pytest.mark.parametrize("adjusted", [False, True])
@pytest.mark.parametrize("plan", PLANS, ids=lambda p: f"{p.dispatch}-{p.matmul}")
def test_downdate_update_roundtrip(adjusted, plan):
    """downdate(update(state, x), last) == state to <= 1e-10 in f64, for
    both Algorithms and both dispatch modes (ISSUE acceptance bound)."""
    engine, st, X = _grow(adjusted, plan)
    x_new = jnp.asarray(RNG.normal(size=4))
    st1 = engine.update(st, x_new)
    st2 = engine.downdate(st1, int(st1.m) - 1)
    m = int(st.m)
    assert int(st2.m) == m
    np.testing.assert_allclose(np.asarray(st2.L[:m]), np.asarray(st.L[:m]),
                               atol=1e-10)
    np.testing.assert_allclose(
        np.asarray(rankone.reconstruct(st2.L, st2.U, st2.m)),
        np.asarray(rankone.reconstruct(st.L, st.U, st.m)), atol=1e-10)
    np.testing.assert_allclose(np.asarray(st2.K1), np.asarray(st.K1),
                               atol=1e-10)
    np.testing.assert_allclose(float(st2.S), float(st.S), atol=1e-9)
    np.testing.assert_allclose(np.asarray(st2.X), np.asarray(st.X),
                               atol=1e-12)


@pytest.mark.parametrize("adjusted", [False, True])
def test_downdate_interior_matches_batch(adjusted):
    """Removing an interior point must leave exactly the batch (centered)
    gram eigensystem of the surviving points."""
    engine, st, X = _grow(adjusted, eng.UpdatePlan())
    st2 = engine.downdate(st, 2)
    keep = [i for i in range(11) if i != 2]
    Xk = jnp.asarray(X[keep])
    K = kf.gram_block(Xk, Xk, spec=SPEC)
    Keff = np.asarray(kf.center_gram(K)) if adjusted else np.asarray(K)
    m = int(st2.m)
    rec = np.asarray(rankone.reconstruct(st2.L, st2.U, st2.m))[:m, :m]
    np.testing.assert_allclose(rec, Keff, atol=1e-10)
    # survivors keep their arrival order
    np.testing.assert_allclose(np.asarray(st2.X[:m]), np.asarray(Xk),
                               atol=0)


def test_downdate_preserves_padding_invariants():
    """Post-downdate state must satisfy every invariant the kernels'
    active-tile pruning assumes: inactive columns exactly identity,
    active columns zero on rows >= m, L sentinels ascending on top,
    U orthogonal."""
    engine, st, _ = _grow(True, eng.UpdatePlan(dispatch="bucketed",
                                               min_bucket=8))
    st2 = engine.downdate(st, 4)
    M = st2.L.shape[0]
    m = int(st2.m)
    U = np.asarray(st2.U)
    np.testing.assert_array_equal(U[:, m:], np.eye(M)[:, m:])
    assert np.abs(U[m:, :m]).max() == 0.0
    L = np.asarray(st2.L)
    assert (np.diff(L) > 0).all() or (np.sort(L[:m]) <= L[m:].min()).all()
    assert L[m:].min() > L[:m].max()
    np.testing.assert_allclose(U @ U.T, np.eye(M), atol=1e-12)


def test_downdate_rebuckets_downward_and_keeps_streaming():
    """Bucketed dispatch: downdating across a bucket rung must re-bucket
    the NEXT step downward (cost scales with the shrunk m) and keep
    producing states identical to the fixed-dispatch path."""
    rng = np.random.default_rng(23)
    X = rng.normal(size=(20, 4))
    buk = eng.Engine(SPEC, eng.UpdatePlan(dispatch="bucketed", min_bucket=8),
                     adjusted=True)
    fix = eng.Engine(SPEC, eng.UpdatePlan(), adjusted=True)
    sb = inkpca.init_state(jnp.asarray(X[:4]), 32, SPEC, adjusted=True,
                           dtype=jnp.float64)
    sf = sb
    for i in range(4, 10):        # m=10: inside bucket 16
        sb = buk.update(sb, jnp.asarray(X[i]))
        sf = fix.update(sf, jnp.asarray(X[i]))
    for _ in range(3):            # back below the 8-rung: m=7
        sb = buk.downdate(sb, 0)
        sf = fix.downdate(sf, 0)
    assert eng.bucket_for(int(sb.m) + 1, 32, 8) == 8   # re-buckets at 8
    for i in range(10, 20):       # stream on, crossing 8 -> 16 again
        sb = buk.update(sb, jnp.asarray(X[i]))
        sf = fix.update(sf, jnp.asarray(X[i]))
    m = int(sb.m)
    assert m == int(sf.m) == 17
    np.testing.assert_allclose(
        np.asarray(rankone.reconstruct(sb.L, sb.U, sb.m)),
        np.asarray(rankone.reconstruct(sf.L, sf.U, sf.m)), atol=1e-9)


def test_engine_replace_swaps_point():
    """replace(i, x) must equal the batch eigensystem of the point set
    with X[i] swapped for x — on a FULL state (downdate frees the slot)."""
    rng = np.random.default_rng(29)
    X = rng.normal(size=(8, 3))
    engine = eng.Engine(SPEC, eng.UpdatePlan(), adjusted=True)
    st = inkpca.init_state(jnp.asarray(X[:4]), 8, SPEC, adjusted=True,
                          dtype=jnp.float64)
    for i in range(4, 8):
        st = engine.update(st, jnp.asarray(X[i]))
    assert int(st.m) == 8         # full: plain update would raise
    x_new = jnp.asarray(rng.normal(size=3))
    st2 = engine.replace(st, 3, x_new)
    Xk = np.concatenate([X[[0, 1, 2, 4, 5, 6, 7]], np.asarray(x_new)[None]])
    Keff = np.asarray(kf.center_gram(kf.gram_block(jnp.asarray(Xk),
                                                   jnp.asarray(Xk),
                                                   spec=SPEC)))
    rec = np.asarray(rankone.reconstruct(st2.L, st2.U, st2.m))
    np.testing.assert_allclose(rec, Keff, atol=1e-10)


def test_downdate_validation():
    engine, st, _ = _grow(False, eng.UpdatePlan())
    with pytest.raises(ValueError):
        engine.downdate(st, int(st.m))          # out of active range
    with pytest.raises(ValueError):
        engine.downdate(st, -1)
    small = inkpca.init_state(jnp.asarray(RNG.normal(size=(1, 4))), 8, SPEC,
                              adjusted=False, dtype=jnp.float64)
    with pytest.raises(ValueError):
        engine.downdate(small, 0)               # m < 2


def test_batched_downdate_masked_matches_loop():
    """The vmapped masked downdate (StreamBatch's eviction step) must
    equal per-tenant engine downdates, with inactive tenants bitwise
    untouched."""
    rng = np.random.default_rng(31)
    B, d = 3, 4
    engine = eng.Engine(SPEC, eng.UpdatePlan(), adjusted=True)
    states, X0 = [], rng.normal(size=(B, 9, d))
    for b in range(B):
        st = inkpca.init_state(jnp.asarray(X0[b, :4]), 16, SPEC,
                               adjusted=True, dtype=jnp.float64)
        for i in range(4, 9):
            st = engine.update(st, jnp.asarray(X0[b, i]))
        states.append(st)
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *states)
    rows = jnp.asarray([0, 2, 1], jnp.int32)
    active = jnp.asarray([True, False, True])
    out = eng._batched_downdate_masked(stacked, rows, active, SPEC, True,
                                       eng.UpdatePlan())
    for b in range(B):
        got = jax.tree.map(lambda leaf: leaf[b], out)
        if bool(active[b]):
            ref = engine.downdate(states[b], int(rows[b]))
            np.testing.assert_allclose(np.asarray(got.L), np.asarray(ref.L),
                                       atol=1e-12)
            np.testing.assert_allclose(
                np.asarray(rankone.reconstruct(got.L, got.U, got.m)),
                np.asarray(rankone.reconstruct(ref.L, ref.U, ref.m)),
                atol=1e-11)
        else:
            for a, r in zip(jax.tree.leaves(got),
                            jax.tree.leaves(states[b])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(r))


# ------------------------------------------------------- sharded downdate ---
def _sharded_setup():
    rng = np.random.default_rng(37)
    X = rng.normal(size=(11, 4))
    engine = eng.Engine(SPEC, eng.UpdatePlan(), adjusted=False)
    st = inkpca.init_state(jnp.asarray(X[:4]), 16, SPEC, adjusted=False,
                           dtype=jnp.float64)
    for i in range(4, 11):
        st = engine.update(st, jnp.asarray(X[i]))
    return engine, st


@pytest.mark.parametrize("plan", [
    eng.UpdatePlan(),
    eng.UpdatePlan(dispatch="bucketed", min_bucket=8),
    eng.UpdatePlan(matmul="jnp2", merge_fallback=True),
], ids=lambda p: f"{p.dispatch}-{p.matmul}")
def test_sharded_downdate_matches_local(plan):
    """make_sharded_downdate == Engine.downdate of the boundary point,
    across dispatch modes and the fused pair with merge fallback."""
    from repro.core import distributed as dkpca

    engine, st = _sharded_setup()
    mesh = make_mesh((1,), ("data",))
    ddown = dkpca.make_sharded_downdate(mesh, plan=plan)
    q = int(st.m) - 1
    a = kf.kernel_row(st.X[q], st.X, spec=SPEC)
    a = jnp.where(rankone.active_mask(16, st.m), a, 0.0)
    Ls, Us, ms = ddown(st.L, st.U, a, a[q], st.m)
    ref = engine.downdate(st, q)
    assert int(ms) == int(ref.m)
    np.testing.assert_allclose(np.asarray(Ls[:int(ms)]),
                               np.asarray(ref.L[:int(ms)]), atol=1e-10)
    np.testing.assert_allclose(
        np.asarray(rankone.reconstruct(Ls, Us, ms)),
        np.asarray(rankone.reconstruct(ref.L, ref.U, ref.m)), atol=1e-10)


def test_sharded_downdate_then_update_roundtrip():
    """A sharded update followed by a sharded downdate of the same point
    returns the original sharded (L, U) — the distributed path has the
    same sign-symmetry as the local one."""
    from repro.core import distributed as dkpca

    engine, st = _sharded_setup()
    mesh = make_mesh((1,), ("data",))
    plan = eng.UpdatePlan()
    x_new = jnp.asarray(np.random.default_rng(41).normal(size=4))
    st1 = engine.update(st, x_new)
    ddown = dkpca.make_sharded_downdate(mesh, plan=plan)
    q = int(st1.m) - 1
    a = kf.kernel_row(st1.X[q], st1.X, spec=SPEC)
    a = jnp.where(rankone.active_mask(16, st1.m), a, 0.0)
    Ls, Us, ms = ddown(st1.L, st1.U, a, a[q], st1.m)
    np.testing.assert_allclose(np.asarray(Ls[:int(ms)]),
                               np.asarray(st.L[:int(ms)]), atol=1e-10)
    np.testing.assert_allclose(
        np.asarray(rankone.reconstruct(Ls, Us, ms)),
        np.asarray(rankone.reconstruct(st.L, st.U, st.m)), atol=1e-10)


@pytest.mark.parametrize("plan", [
    eng.UpdatePlan(),
    eng.UpdatePlan(dispatch="bucketed", min_bucket=8),
    eng.UpdatePlan(matmul="jnp2", merge_fallback=True),
], ids=lambda p: f"{p.dispatch}-{p.matmul}")
def test_sharded_evict_arbitrary_row_matches_local(plan):
    """make_sharded_evict (in-graph boundary permutation: ppermute + one
    psum gather along the replicated axis) == Engine.downdate of the SAME
    arbitrary row — no host round-trip decides the victim (the ROADMAP
    sharded-boundary-permutation follow-up)."""
    from repro.core import distributed as dkpca

    engine, st = _sharded_setup()
    mesh = make_mesh((1,), ("data",))
    ev = dkpca.make_sharded_evict(mesh, plan=plan)
    for victim in (0, 3, int(st.m) - 1):
        a = kf.kernel_row(st.X[victim], st.X, spec=SPEC)
        a = jnp.where(rankone.active_mask(16, st.m), a, 0.0)
        Ls, Us, ms = ev(st.L, st.U, a, a[victim], jnp.int32(victim), st.m)
        ref = engine.downdate(st, victim)
        assert int(ms) == int(ref.m)
        np.testing.assert_allclose(np.asarray(Ls[:int(ms)]),
                                   np.asarray(ref.L[:int(ms)]), atol=1e-10)
        np.testing.assert_allclose(
            np.asarray(rankone.reconstruct(Ls, Us, ms)),
            np.asarray(rankone.reconstruct(ref.L, ref.U, ref.m)),
            atol=1e-10)


@pytest.mark.parametrize("dispatch", ["fixed", "bucketed"])
def test_sharded_window_block_matches_local_windowed_stream(dispatch):
    """make_sharded_window_block (scan of in-graph evict+ingest steps,
    victim from the replicated arrival ring) == the local windowed
    stream, state and ring both."""
    from repro.core import distributed as dkpca

    rng = np.random.default_rng(43)
    X = rng.normal(size=(12, 4))
    W = 8
    plan = (eng.UpdatePlan(dispatch="bucketed", min_bucket=8)
            if dispatch == "bucketed" else eng.UpdatePlan())
    stream = inkpca.KPCAStream(jnp.asarray(X[:4]), 16, SPEC,
                               adjusted=False, dtype=jnp.float64,
                               plan=plan, window=W)
    for i in range(4, 12):                       # window exactly full
        stream.update(jnp.asarray(X[i]))
    ws = stream.state
    assert int(ws.kpca.m) == W
    xs = jnp.asarray(rng.normal(size=(5, 4)))
    mesh = make_mesh((1,), ("data",))
    wb = dkpca.make_sharded_window_block(mesh, SPEC, plan=plan)
    L2, U2, X2, ages2, clock2 = wb(ws.kpca.L, ws.kpca.U, ws.kpca.X,
                                   ws.ages, ws.clock, xs, ws.kpca.m)
    for t in range(5):
        stream.update(xs[t])
    ref = stream.state
    np.testing.assert_allclose(np.asarray(L2[:W]),
                               np.asarray(ref.kpca.L[:W]), atol=1e-10)
    np.testing.assert_allclose(
        np.asarray(rankone.reconstruct(L2, U2, jnp.int32(W))),
        np.asarray(rankone.reconstruct(ref.kpca.L, ref.kpca.U,
                                       ref.kpca.m)), atol=1e-10)
    np.testing.assert_allclose(np.asarray(X2), np.asarray(ref.kpca.X),
                               atol=1e-12)
    np.testing.assert_array_equal(np.asarray(ages2), np.asarray(ref.ages))
    assert int(clock2) == int(ref.clock)


def test_sharded_window_block_rebases_near_sentinel():
    """A sharded window block whose clock span would reach the age
    sentinel must rebase the ring at block entry (traced, like the
    local hoisted check) and keep evicting in true FIFO order."""
    from repro.core import distributed as dkpca
    from repro.core import window as wnd

    rng = np.random.default_rng(47)
    X = rng.normal(size=(12, 4))
    W = 8
    stream = inkpca.KPCAStream(jnp.asarray(X[:4]), 16, SPEC,
                               adjusted=False, dtype=jnp.float64, window=W)
    for i in range(4, 12):
        stream.update(jnp.asarray(X[i]))
    ws = stream.state
    sent = wnd.age_sentinel(ws.ages.dtype)
    shift = (sent - 3) - int(ws.clock)         # block of 5 would collide
    aged = ws._replace(ages=jnp.where(ws.ages == sent, sent,
                                      ws.ages + shift),
                       clock=ws.clock + shift)
    xs = jnp.asarray(rng.normal(size=(5, 4)))
    mesh = make_mesh((1,), ("data",))
    wb = dkpca.make_sharded_window_block(mesh, SPEC, plan=eng.UpdatePlan())
    L2, U2, X2, ages2, clock2 = wb(aged.kpca.L, aged.kpca.U, aged.kpca.X,
                                   aged.ages, aged.clock, xs, aged.kpca.m)
    assert int(clock2) < sent // 2             # rebased at block entry
    # eigensystem still matches the local windowed stream (rebasing never
    # touches the kpca state), and the FIFO order survives
    stream.state = aged
    for t in range(5):
        stream.update(xs[t])
    ref = stream.state
    np.testing.assert_allclose(
        np.asarray(rankone.reconstruct(L2, U2, jnp.int32(W))),
        np.asarray(rankone.reconstruct(ref.kpca.L, ref.kpca.U,
                                       ref.kpca.m)), atol=1e-10)
    np.testing.assert_array_equal(np.argsort(np.asarray(ages2[:W])),
                                  np.argsort(np.asarray(ref.ages[:W])))
