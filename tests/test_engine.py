"""Engine layer (repro.core.engine): UpdatePlan routing, bucketed
slice/update/scatter, shrink compaction, and vmapped multi-tenant
streaming — all consumers share this one code path."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import engine as eng, inkpca, kernels_fn as kf, rankone
from repro.distributed.sharding import make_mesh

RNG = np.random.default_rng(17)
SPEC = kf.KernelSpec(name="rbf", sigma=5.0)


# ----------------------------------------------------------- UpdatePlan ---
def test_plan_fused_and_inner_matmul():
    assert not eng.UpdatePlan(matmul="jnp").fused
    assert not eng.UpdatePlan(matmul="pallas").fused
    assert eng.UpdatePlan(matmul="jnp2").fused
    assert eng.UpdatePlan(matmul="pallas2").fused
    assert eng.UpdatePlan(matmul="jnp2").inner_matmul == "jnp"
    assert eng.UpdatePlan(matmul="pallas2").inner_matmul == "pallas"
    assert eng.UpdatePlan(matmul="pallas").inner_matmul == "pallas"


def test_kernel_plan_normalizes_dispatch_fields():
    """Jitted updates must cache once per numerics, not per bucket ladder."""
    a = eng.UpdatePlan(dispatch="bucketed", min_bucket=8).kernel_plan()
    b = eng.UpdatePlan(dispatch="fixed", min_bucket=64).kernel_plan()
    assert a == b
    assert hash(a) == hash(b)       # usable as a jit static argument


def test_resolve_iters_by_dtype():
    assert eng.resolve_iters(None, jnp.float64) == 62
    assert eng.resolve_iters(None, jnp.float32) == 32
    assert eng.resolve_iters(17, jnp.float32) == 17


# ----------------------------------------------- engine stream dispatch ---
def test_engine_bucketed_stream_matches_fixed():
    X = RNG.normal(size=(24, 4))
    fix = eng.Engine(SPEC, eng.UpdatePlan(), adjusted=True)
    buk = eng.Engine(SPEC, eng.UpdatePlan(dispatch="bucketed", min_bucket=8),
                     adjusted=True)
    s_fix = inkpca.init_state(jnp.asarray(X[:4]), 32, SPEC, adjusted=True,
                              dtype=jnp.float64)
    s_buk = s_fix
    for i in range(4, 14):
        s_fix = fix.update(s_fix, jnp.asarray(X[i]))
        s_buk = buk.update(s_buk, jnp.asarray(X[i]))
    s_fix = fix.update_block(s_fix, jnp.asarray(X[14:]))
    s_buk = buk.update_block(s_buk, jnp.asarray(X[14:]))
    assert int(s_fix.m) == int(s_buk.m) == 24
    np.testing.assert_allclose(
        np.asarray(rankone.reconstruct(s_buk.L, s_buk.U, s_buk.m)),
        np.asarray(rankone.reconstruct(s_fix.L, s_fix.U, s_fix.m)),
        atol=1e-8)


def test_engine_fused_plan_matches_sequential():
    X = RNG.normal(size=(16, 3))
    seq = eng.Engine(SPEC, eng.UpdatePlan(matmul="jnp"), adjusted=True)
    fus = eng.Engine(SPEC, eng.UpdatePlan(matmul="jnp2"), adjusted=True)
    s0 = inkpca.init_state(jnp.asarray(X[:4]), 16, SPEC, adjusted=True,
                           dtype=jnp.float64)
    s1 = seq.update_block(s0, jnp.asarray(X[4:]))
    s2 = fus.update_block(s0, jnp.asarray(X[4:]))
    np.testing.assert_allclose(
        np.asarray(rankone.reconstruct(s2.L, s2.U, s2.m)),
        np.asarray(rankone.reconstruct(s1.L, s1.U, s1.m)), atol=1e-7)


# ------------------------------------------------- truncate / compaction ---
def _grown_stream(n=16, capacity=64, adjusted=False):
    X = RNG.normal(size=(n, 4))
    st = inkpca.KPCAStream(jnp.asarray(X[:4]), capacity, SPEC,
                           adjusted=adjusted, dtype=jnp.float64,
                           dispatch="bucketed", min_bucket=8)
    st.update_block(jnp.asarray(X[4:]))
    return st, X


def test_compact_shapes_shrink_to_bucket():
    """The satellite claim: compaction frees the old large bucket — the
    state's arrays really are re-allocated at the active bucket."""
    st, _ = _grown_stream(n=16, capacity=64)
    st.truncate(6, compact=True)
    Mb = eng.bucket_for(7, 64, 8)           # = 8
    assert st.state.L.shape == (Mb,)
    assert st.state.U.shape == (Mb, Mb)
    assert st.state.K1.shape == (Mb,)
    assert st.state.X.shape == (Mb, 4)
    assert int(st.state.m) == 6
    assert bool(jnp.isfinite(st.state.L).all())


def test_compact_exact_for_prefix_supported_state():
    """For a never-truncated stream (support is already a prefix) compaction
    is a pure re-allocation: the active block reconstruction is unchanged."""
    st, _ = _grown_stream(n=12, capacity=64)
    m = int(st.state.m)
    before = np.asarray(st.engine.compact(st.state).L[:m])
    rec0 = np.asarray(rankone.reconstruct(st.state.L, st.state.U,
                                          st.state.m))[:m, :m]
    comp = st.engine.compact(st.state)
    rec1 = np.asarray(rankone.reconstruct(comp.L, comp.U, comp.m))[:m, :m]
    np.testing.assert_allclose(rec1, rec0, atol=1e-9)
    np.testing.assert_allclose(np.sort(before), np.sort(np.asarray(
        st.state.L[:m])), atol=1e-9)


def test_truncate_without_compact_keeps_bucketed_correct():
    """Post-truncate, kept eigenvectors have support on the OLD rows; the
    engine must keep bucketing at the support floor or results diverge
    from the fixed path."""
    X = RNG.normal(size=(26, 4))
    fix = inkpca.KPCAStream(jnp.asarray(X[:4]), 64, SPEC, adjusted=False,
                            dtype=jnp.float64)
    buk = inkpca.KPCAStream(jnp.asarray(X[:4]), 64, SPEC, adjusted=False,
                            dtype=jnp.float64, dispatch="bucketed",
                            min_bucket=8)
    fix.update_block(jnp.asarray(X[4:18]))
    buk.update_block(jnp.asarray(X[4:18]))
    fix.truncate(5)
    buk.truncate(5)
    fix.update_block(jnp.asarray(X[18:]))
    buk.update_block(jnp.asarray(X[18:]))
    np.testing.assert_allclose(np.asarray(buk.reconstruction()),
                               np.asarray(fix.reconstruction()), atol=1e-8)


def test_truncate_with_compact_keeps_streaming_until_exhaustion():
    """A compacted state keeps streaming inside its new (smaller) capacity
    and raises — rather than silently clamping — once it fills up."""
    st, X = _grown_stream(n=16, capacity=64)
    st.truncate(6, compact=True)            # re-allocated at bucket 8
    st.update_block(jnp.asarray(RNG.normal(size=(2, 4))))
    assert int(st.state.m) == 8
    assert bool(jnp.isfinite(st.state.L).all())
    assert bool(jnp.isfinite(st.state.U).all())
    with pytest.raises(ValueError):
        st.update(jnp.asarray(RNG.normal(size=(4,))))
    # an explicit compaction capacity leaves room to keep growing
    st2, _ = _grown_stream(n=16, capacity=64)
    st2.truncate(6, compact=True)
    st2.state = st2.engine.compact(st2.state, capacity=32)
    st2.update_block(jnp.asarray(RNG.normal(size=(8, 4))))
    assert int(st2.state.m) == 14


def test_engine_truncate_default_is_safe_for_direct_callers():
    """Bare engine.truncate on a bucketed engine must leave a state that
    streams correctly WITHOUT any min_rows bookkeeping (support folded to
    a prefix at unchanged capacity)."""
    X = RNG.normal(size=(24, 4))
    engine = eng.Engine(SPEC, eng.UpdatePlan(dispatch="bucketed",
                                             min_bucket=8), adjusted=False)
    state = inkpca.init_state(jnp.asarray(X[:4]), 64, SPEC, adjusted=False,
                              dtype=jnp.float64)
    state = engine.update_block(state, jnp.asarray(X[4:18]))
    state = engine.truncate(state, 5)       # default: compact, same capacity
    assert state.L.shape == (64,)           # capacity unchanged
    # support is a prefix again: rows >= 5 of active columns are zero
    assert float(jnp.abs(state.U[5:, :5]).max()) < 1e-12
    state = engine.update_block(state, jnp.asarray(X[18:]))
    assert bool(jnp.isfinite(state.L).all())
    rec = rankone.reconstruct(state.L, state.U, state.m)
    assert bool(jnp.isfinite(rec).all())


def test_compact_capacity_must_hold_active_set():
    st, _ = _grown_stream(n=12, capacity=64)
    with pytest.raises(ValueError):
        st.engine.compact(st.state, capacity=int(st.state.m))


# ------------------------------------------------------ multi-tenant batch --
def _tenant_setup(B=3, capacity=32, min_bucket=8, n=12, d=5):
    x0 = jnp.asarray(RNG.normal(size=(B, 4, d)))
    plan = eng.UpdatePlan(dispatch="bucketed", min_bucket=min_bucket)
    batch = eng.StreamBatch(x0, capacity, SPEC, plan=plan, adjusted=True,
                            dtype=jnp.float64)
    streams = [inkpca.KPCAStream(x0[i], capacity, SPEC, adjusted=True,
                                 dtype=jnp.float64, plan=plan)
               for i in range(B)]
    X = jnp.asarray(RNG.normal(size=(n, B, d)))
    return batch, streams, X


def test_streambatch_matches_per_tenant_loop():
    batch, streams, X = _tenant_setup()
    for t in range(X.shape[0]):
        batch.update(X[t])
        for i, s in enumerate(streams):
            s.update(X[t, i])
    for i, s in enumerate(streams):
        st = batch.state_of(i)
        np.testing.assert_allclose(np.asarray(st.L), np.asarray(s.state.L),
                                   atol=1e-9)
        np.testing.assert_allclose(
            np.asarray(rankone.reconstruct(st.L, st.U, st.m)),
            np.asarray(s.reconstruction()), atol=1e-8)


def test_streambatch_update_block_matches_stepwise():
    batch, streams, X = _tenant_setup()
    batch.update_block(X)
    for i, s in enumerate(streams):
        s.update_block(X[:, i])
        np.testing.assert_allclose(np.asarray(batch.state_of(i).L),
                                   np.asarray(s.state.L), atol=1e-9)


def test_streambatch_active_mask_diverges_tenants():
    batch, _, X = _tenant_setup(B=3)
    batch.update(X[0])
    before = np.asarray(batch.state_of(1).L)
    batch.update(X[1], active=jnp.asarray([True, False, True]))
    ms = [int(v) for v in np.asarray(batch.states.m)]
    assert ms == [6, 5, 6]
    # idle tenant's state is bitwise untouched by the masked step
    np.testing.assert_array_equal(np.asarray(batch.state_of(1).L), before)


def test_streambatch_transform_shape_and_finite():
    batch, _, X = _tenant_setup(B=3, d=5)
    batch.update_block(X)
    q = jnp.asarray(RNG.normal(size=(3, 4, 5)))
    y = batch.transform(q, n_components=3)
    assert y.shape == (3, 4, 3)
    assert bool(jnp.isfinite(y).all())


def test_streambatch_capacity_exhaustion_raises():
    x0 = jnp.asarray(RNG.normal(size=(2, 4, 3)))
    plan = eng.UpdatePlan(dispatch="bucketed", min_bucket=4)
    batch = eng.StreamBatch(x0, 8, SPEC, plan=plan, dtype=jnp.float64)
    batch.update_block(jnp.asarray(RNG.normal(size=(4, 2, 3))))
    with pytest.raises(ValueError):
        batch.update(jnp.asarray(RNG.normal(size=(2, 3))))


def test_streambatch_rejects_non_batched_seeds():
    with pytest.raises(ValueError):
        eng.StreamBatch(jnp.zeros((4, 3)), 16, SPEC)


# ------------------------------------------- bucket-homogeneous cohorts ---
def _mixed_batches(cohorts, B=6, d=4, capacity=64):
    rng = np.random.default_rng(23)
    plan = eng.UpdatePlan(dispatch="bucketed", min_bucket=8)
    seeds = jnp.asarray(rng.normal(size=(B, 3, d)))
    batch = eng.StreamBatch(seeds, capacity, SPEC, plan=plan, adjusted=True,
                            dtype=jnp.float64, cohorts=cohorts)
    streams = [inkpca.KPCAStream(seeds[i], capacity, SPEC, adjusted=True,
                                 dtype=jnp.float64, plan=plan)
               for i in range(B)]
    return batch, streams, rng


def test_streambatch_bucket_cohorts_match_per_tenant_loop():
    """Bucket-homogeneous cohorts (masked updates diverging tenant sizes,
    then a block) must equal B independent Python-loop streams."""
    batch, streams, rng = _mixed_batches("bucket")
    B, d = len(streams), 4
    for step in range(18):
        xs = jnp.asarray(rng.normal(size=(B, d)))
        active = np.array([(step % (i + 1)) == 0 for i in range(B)])
        batch.update(xs, active=jnp.asarray(active))
        for i, s in enumerate(streams):
            if active[i]:
                s.update(xs[i])
    xs_blk = jnp.asarray(rng.normal(size=(6, B, d)))
    batch.update_block(xs_blk)
    for i, s in enumerate(streams):
        s.update_block(xs_blk[:, i])
    # the cohort actually split into >1 bucket group
    assert batch._groups is not None and len(batch._groups) > 1
    assert len({g["Mb"] for g in batch._groups}) == len(batch._groups)
    sts = batch.states
    for i, s in enumerate(streams):
        np.testing.assert_allclose(np.asarray(sts.L[i]),
                                   np.asarray(s.state.L), atol=1e-9)
        np.testing.assert_allclose(
            np.asarray(rankone.reconstruct(sts.L[i], sts.U[i], sts.m[i])),
            np.asarray(s.reconstruction()), atol=1e-8)


def test_streambatch_bucket_cohorts_transform_matches_max():
    """transform() must agree between cohort geometries (same states)."""
    rng = np.random.default_rng(29)
    B, d = 4, 4
    seeds = jnp.asarray(rng.normal(size=(B, 3, d)))
    plan = eng.UpdatePlan(dispatch="bucketed", min_bucket=8)
    kw = dict(plan=plan, adjusted=True, dtype=jnp.float64)
    a = eng.StreamBatch(seeds, 32, SPEC, cohorts="max", **kw)
    b = eng.StreamBatch(seeds, 32, SPEC, cohorts="bucket", **kw)
    xs = jnp.asarray(rng.normal(size=(8, B, d)))
    a.update_block(xs)
    b.update_block(xs)
    q = jnp.asarray(rng.normal(size=(B, 5, d)))
    ya = a.transform(q, n_components=3)
    yb = b.transform(q, n_components=3)
    np.testing.assert_allclose(np.asarray(yb), np.asarray(ya), atol=1e-8)


def test_streambatch_bucket_cohorts_capacity_exhaustion_raises():
    rng = np.random.default_rng(31)
    x0 = jnp.asarray(rng.normal(size=(2, 4, 3)))
    plan = eng.UpdatePlan(dispatch="bucketed", min_bucket=4)
    batch = eng.StreamBatch(x0, 8, SPEC, plan=plan, dtype=jnp.float64,
                            cohorts="bucket")
    batch.update_block(jnp.asarray(rng.normal(size=(4, 2, 3))))
    with pytest.raises(ValueError):
        batch.update(jnp.asarray(rng.normal(size=(2, 3))))


def test_streambatch_bucket_padded_identical_states():
    """ISSUE satellite: padded and unpadded cohorts produce IDENTICAL
    states — pad lanes are masked out of every step and never scattered
    back (bitwise equality, masked updates + scans + regroup crossings)."""
    rng = np.random.default_rng(43)
    B, d = 6, 4
    seeds = jnp.asarray(rng.normal(size=(B, 3, d)))
    plan = eng.UpdatePlan(dispatch="bucketed", min_bucket=8)
    kw = dict(plan=plan, adjusted=True, dtype=jnp.float64)
    a = eng.StreamBatch(seeds, 64, SPEC, cohorts="bucket", **kw)
    b = eng.StreamBatch(seeds, 64, SPEC, cohorts="bucket-padded", **kw)
    padded_seen = False
    for step in range(18):
        xs = jnp.asarray(rng.normal(size=(B, d)))
        act = np.array([(step % (i + 1)) == 0 for i in range(B)])
        a.update(xs, active=jnp.asarray(act))
        b.update(xs, active=jnp.asarray(act))
        padded_seen |= any(len(g["idx_pad"]) > g["n_real"]
                           for g in b._groups)
    xs_blk = jnp.asarray(rng.normal(size=(6, B, d)))
    a.update_block(xs_blk)
    b.update_block(xs_blk)
    # padding really happened at some point, and sizes stay powers of two
    assert padded_seen
    for g in b._groups:
        size = len(g["idx_pad"])
        assert size & (size - 1) == 0
    for la, lb in zip(jax.tree.leaves(a.states), jax.tree.leaves(b.states)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    # transform agrees too (pad lanes sliced off)
    q = jnp.asarray(rng.normal(size=(B, 4, d)))
    np.testing.assert_allclose(np.asarray(b.transform(q, n_components=3)),
                               np.asarray(a.transform(q, n_components=3)),
                               atol=1e-12)


def test_streambatch_bucket_padded_bounded_compile_keys():
    """Padded group sizes take at most log2(B)+1 distinct values per
    bucket, whatever churn does to group cuts (the recompile bound)."""
    sizes = set()
    rng = np.random.default_rng(47)
    B = 7
    seeds = jnp.asarray(rng.normal(size=(B, 3, 3)))
    plan = eng.UpdatePlan(dispatch="bucketed", min_bucket=8)
    batch = eng.StreamBatch(seeds, 32, SPEC, plan=plan, adjusted=True,
                            dtype=jnp.float64, cohorts="bucket-padded")
    for step in range(16):
        xs = jnp.asarray(rng.normal(size=(B, 3)))
        act = np.array([(step % (i + 2)) != 0 for i in range(B)])
        batch.update(xs, active=jnp.asarray(act))
        for g in batch._groups:
            sizes.add((len(g["idx_pad"]), g["Mb"]))
    pad_sizes = {s for s, _ in sizes}
    assert all(s & (s - 1) == 0 for s in pad_sizes)
    assert len(pad_sizes) <= int(np.ceil(np.log2(B))) + 1


# ------------------------------------- Nyström truncate/compact guard ---
def test_nystrom_truncate_compact_preserves_observed_rows():
    """Engine.truncate(compact=True) on a grow_rows Nyström state must keep
    every observed row/landmark (row-support clamp) while shrinking
    capacity, and reproduce the uncompacted truncated reconstruction."""
    from repro.core import nystrom

    rng = np.random.default_rng(37)
    d, cap = 4, 64
    engine = eng.Engine(SPEC, eng.UpdatePlan(dispatch="bucketed",
                                             min_bucket=8), adjusted=False)
    x0 = jnp.asarray(rng.normal(size=(4, d)))
    st = nystrom.init_nystrom(None, x0, cap, SPEC, dtype=jnp.float64,
                              grow_rows=True)
    for _ in range(16):
        st = engine.add_landmark(st, None, jnp.asarray(rng.normal(size=d)))
    st = nystrom.observe_rows(st, jnp.asarray(rng.normal(size=(10, d))),
                              SPEC)
    n_rows, m_before = st.Knm.shape[0], int(st.kpca.m)

    t_nc = engine.truncate(st, 8, compact=False)
    t_c = engine.truncate(st, 8, compact=True)
    # observed rows and landmark support survive; capacity shrinks
    assert t_c.Knm.shape[0] == n_rows
    assert t_c.Xrows.shape == st.Xrows.shape
    assert int(t_c.kpca.m) == m_before
    assert t_c.kpca.L.shape[0] < cap
    np.testing.assert_allclose(
        np.asarray(nystrom.reconstruct_tilde(t_c)),
        np.asarray(nystrom.reconstruct_tilde(t_nc)), atol=1e-10)
    # streaming continues on the compacted state
    t2 = nystrom.observe_rows(t_c, jnp.asarray(rng.normal(size=(2, d))),
                              SPEC)
    t2 = engine.add_landmark(t2, None, jnp.asarray(rng.normal(size=d)))
    assert bool(jnp.isfinite(nystrom.reconstruct_tilde(t2)).all())
    # explicit capacity below the row-support floor is refused
    with pytest.raises(ValueError):
        engine.truncate(st, 8, compact=True, capacity=16)


def test_nystrom_uncompacted_truncate_add_landmark_min_rows():
    """After an UNcompacted Nyström truncate, bucketed add_landmark must
    honor the row-support floor (min_rows = pre-truncation landmark
    count) and then match the fixed-dispatch reference exactly."""
    from repro.core import nystrom

    rng = np.random.default_rng(41)
    d, cap = 4, 64
    buk = eng.Engine(SPEC, eng.UpdatePlan(dispatch="bucketed",
                                          min_bucket=8), adjusted=False)
    fix = eng.Engine(SPEC, eng.UpdatePlan(), adjusted=False)
    x0 = jnp.asarray(rng.normal(size=(4, d)))
    st = nystrom.init_nystrom(None, x0, cap, SPEC, dtype=jnp.float64,
                              grow_rows=True)
    for _ in range(16):
        st = buk.add_landmark(st, None, jnp.asarray(rng.normal(size=d)))
    r = int(st.kpca.m)
    t = buk.truncate(st, 8, compact=False)
    x_new = [jnp.asarray(rng.normal(size=d)) for _ in range(3)]
    a = b = t
    for x in x_new:
        a = buk.add_landmark(a, None, x, min_rows=r)
        b = fix.add_landmark(b, None, x)
    np.testing.assert_allclose(
        np.asarray(nystrom.reconstruct_tilde(a)),
        np.asarray(nystrom.reconstruct_tilde(b)), atol=1e-9)


def test_sharded_bucketed_update_full_capacity_state():
    """A full state (m == M) still receives rank-one corrections: the
    bucketed sharded dispatcher must not demand room for m+1."""
    from repro.core import distributed as dkpca, rankone

    rng = np.random.default_rng(43)
    M = 16
    A = rng.normal(size=(M, M)); A = A @ A.T
    lam, vec = np.linalg.eigh(A)
    L = jnp.asarray(np.sort(lam))
    U = jnp.asarray(vec)
    v = jnp.asarray(rng.normal(size=M))
    mesh = make_mesh((1,), ("data",))
    upd = dkpca.make_sharded_update(
        mesh, plan=eng.UpdatePlan(dispatch="bucketed", min_bucket=8))
    Ls, Us = upd(L, U, v, jnp.float64(1.7), jnp.int32(M))
    Ll, Ul = rankone.rank_one_update(L, U, v, jnp.float64(1.7),
                                     jnp.int32(M))
    np.testing.assert_allclose(np.asarray(Ls), np.asarray(Ll), atol=1e-10)
    np.testing.assert_allclose(
        np.asarray(rankone.reconstruct(Ls, Us, jnp.int32(M))),
        np.asarray(rankone.reconstruct(Ll, Ul, jnp.int32(M))), atol=1e-8)
