"""Decoupled serving: published snapshots, double buffering, the
projection kernel, and the tenant-axis mesh builders.

The serving contract under test (core/serving.py):

* ``engine.transform_state`` IS publish-then-query, so frozen-state
  transforms and snapshot queries are bit-identical by construction —
  regardless of kernel path (fused / masked-gram reference).
* Snapshots are immutable jax arrays: concurrent ingest into the working
  state can never perturb a query against a published snapshot, and the
  order of (swap, query) around a retained generation doesn't matter.
* The double-buffered (working state, snapshot) pair checkpoints and
  resumes mid-block at 1e-12.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as eng
from repro.core import inkpca, kernels_fn as kf, krr, nystrom, serving

SPEC = kf.KernelSpec(name="rbf", sigma=2.0)


def _stream(n0=4, d=5, capacity=64, seed=0, **kw):
    rng = np.random.default_rng(seed)
    x0 = jnp.asarray(rng.normal(size=(n0, d)))
    return (inkpca.KPCAStream(x0, capacity, SPEC, adjusted=True,
                              dtype=jnp.float64, **kw), rng, d)


def _bits_equal(a, b):
    return (np.asarray(a) == np.asarray(b)).all()


@pytest.mark.parametrize("fuse", [False, True])
def test_transform_is_publish_query(fuse):
    """Frozen-state transform == snapshot query, bit for bit, on both
    kernel paths."""
    plan = eng.DEFAULT_PLAN._replace(fuse_krow=fuse)
    stream, rng, d = _stream(plan=plan)
    for _ in range(6):
        stream.update(jnp.asarray(rng.normal(size=(d,))))
    st = stream.kpca_state
    q = jnp.asarray(rng.normal(size=(7, d)))
    y1 = eng.transform_state(st, q, n_components=4, spec=SPEC, plan=plan,
                            adjusted=True)
    snap = serving.publish_transform(st, n_components=4, adjusted=True)
    y2 = serving.query(snap, q, spec=SPEC, plan=plan)
    assert _bits_equal(y1, y2)


def test_snapshot_immutable_under_ingest():
    """Queries against a published snapshot are bit-identical no matter
    how much concurrent ingest hits the working state."""
    stream, rng, d = _stream()
    for _ in range(5):
        stream.update(jnp.asarray(rng.normal(size=(d,))))
    buf = serving.DoubleBuffer(stream.kpca_state, n_components=4)
    q = jnp.asarray(rng.normal(size=(6, d)))
    y0 = np.asarray(buf.query(q, spec=SPEC))
    for _ in range(8):                       # ingest into A; B untouched
        stream.update(jnp.asarray(rng.normal(size=(d,))))
        assert _bits_equal(buf.query(q, spec=SPEC), y0)
    # After republishing from the mutated state, queries see the new
    # eigensystem (and match its frozen transform exactly).
    buf.publish(stream.kpca_state)
    y1 = buf.query(q, spec=SPEC)
    assert not _bits_equal(y1, y0)
    assert _bits_equal(
        y1, eng.transform_state(stream.kpca_state, q, n_components=4,
                                spec=SPEC, adjusted=True))


def test_swap_then_query_commutes():
    """swap-then-query == query-then-swap on the published generation: a
    retained snapshot handle answers identically before and after the
    next publish (one publish ahead is the double-buffer guarantee; the
    handle retired two publishes back gets donated)."""
    stream, rng, d = _stream()
    for _ in range(5):
        stream.update(jnp.asarray(rng.normal(size=(d,))))
    buf = serving.DoubleBuffer(stream.kpca_state, n_components=4)
    snap_g = buf.front
    q = jnp.asarray(rng.normal(size=(6, d)))
    y_before = np.asarray(serving.query(snap_g, q, spec=SPEC))

    stream.update(jnp.asarray(rng.normal(size=(d,))))
    buf.publish(stream.kpca_state)           # swap: generation g+1 live
    y_after = serving.query(snap_g, q, spec=SPEC)
    assert _bits_equal(y_before, y_after)
    assert int(buf.front.generation) == int(snap_g.generation) + 1


def test_double_buffer_checkpoint_roundtrip_mid_block():
    """Checkpointing the (working state, published snapshot) pair
    MID-BLOCK — snapshot one generation stale — resumes to the same
    service trajectory at 1e-12."""
    from repro.checkpoint import npz_store

    plan = eng.DEFAULT_PLAN
    stream, rng, d = _stream()
    for _ in range(6):
        stream.update(jnp.asarray(rng.normal(size=(d,))))
    buf = serving.DoubleBuffer(stream.kpca_state, n_components=4)
    # Mid-block: ingest past the publish point without republishing.
    tail = [jnp.asarray(rng.normal(size=(d,))) for _ in range(3)]
    for x in tail:
        stream.update(x)

    ckpt_dir = "/tmp/test_serving_ckpt"
    pair = {"state": stream.kpca_state, "snap": buf.front}
    npz_store.save_checkpoint(ckpt_dir, 0, pair)
    restored = npz_store.load_checkpoint(
        ckpt_dir, 0, jax.tree.map(jnp.zeros_like, pair))

    q = jnp.asarray(rng.normal(size=(5, d)))
    more = [jnp.asarray(rng.normal(size=(d,))) for _ in range(3)]

    def finish(state, snap):
        y_stale = serving.query(snap, q, spec=SPEC)     # pre-swap reads
        for x in more:
            state = inkpca.ingest_adjusted(state, x, spec=SPEC, plan=plan)
        snap = serving.publish_transform(
            state, n_components=4, adjusted=True,
            generation=snap.generation + 1)
        return y_stale, serving.query(snap, q, spec=SPEC), snap

    ys1, yn1, s1 = finish(stream.kpca_state, buf.front)
    ys2, yn2, s2 = finish(restored["state"], restored["snap"])
    assert float(jnp.abs(ys1 - ys2).max()) < 1e-12
    assert float(jnp.abs(yn1 - yn2).max()) < 1e-12
    assert int(s1.generation) == int(s2.generation)


def test_krr_and_nystrom_snapshot_heads():
    """The KRR / Nyström snapshot heads reproduce their per-call query
    paths exactly (same contraction, hoisted to publication)."""
    rng = np.random.default_rng(3)
    d = 4
    x0 = jnp.asarray(rng.normal(size=(4, d)))
    y0 = jnp.asarray(rng.normal(size=(4,)))
    kst = krr.init_krr(x0, y0, 32, SPEC)
    for _ in range(5):
        kst = krr.add_point(kst, jnp.asarray(rng.normal(size=(d,))),
                            float(rng.normal()), SPEC)
    xq = jnp.asarray(rng.normal(size=(6, d)))
    lam = 0.1
    snap = krr.publish_predict(kst, lam)
    assert _bits_equal(krr.snapshot_predict(snap, xq, SPEC),
                       krr.predict(kst, xq, lam, SPEC))

    nst = nystrom.init_nystrom(None, x0, 32, SPEC, dtype=jnp.float64,
                               grow_rows=True)
    for _ in range(5):
        x = jnp.asarray(rng.normal(size=(d,)))
        nst = nystrom.observe_rows(nst, x, SPEC)
        nst = nystrom.add_landmark(nst, None, x, SPEC)
    n = int(nst.Knm.shape[0])
    fsnap = nystrom.publish_features(nst, n)
    assert _bits_equal(nystrom.snapshot_features(fsnap, xq, SPEC),
                       nystrom.query_features(nst, xq, n, SPEC))


def test_stream_batch_publish_matches_transform():
    """Tenant-stacked snapshots from ``StreamBatch.publish`` answer
    ``query_batch`` bit-identically to the engine's frozen transform."""
    rng = np.random.default_rng(4)
    B, d = 3, 5
    plan = eng.DEFAULT_PLAN._replace(serve_components=4)
    sb = eng.StreamBatch(jnp.asarray(rng.normal(size=(B, 4, d))), 64, SPEC,
                         plan=plan, adjusted=True, dtype=jnp.float64)
    for _ in range(4):
        sb.update(jnp.asarray(rng.normal(size=(B, d))))
    snaps = sb.publish()
    q = jnp.asarray(rng.normal(size=(B, 6, d)))
    y = serving.query_batch(snaps, q, spec=SPEC, plan=plan)
    assert _bits_equal(y, sb.transform(q, n_components=4))
    assert list(np.asarray(snaps.generation)) == [0] * B
    assert list(np.asarray(sb.publish().generation)) == [1] * B


@pytest.mark.parametrize("fuse", [False, True])
def test_loop_queries_run_one_compiled_program(fuse):
    """After one warm query, ``IngestServeLoop.query`` books no JAX trace
    and no compile to ``kpca.query`` — across two republishes too — and
    answers bit for bit as the head's per-tenant ``vmap`` of ``query``
    and the batch's frozen transform do."""
    from repro import obs
    from repro.launch.serve import IngestServeLoop

    rng = np.random.default_rng(8)
    B, d = 3, 5
    plan = eng.DEFAULT_PLAN._replace(serve_components=4, fuse_krow=fuse)
    sb = eng.StreamBatch(jnp.asarray(rng.normal(size=(B, 4, d))), 64, SPEC,
                         plan=plan, adjusted=True, dtype=jnp.float64)
    hub = obs.TelemetryHub()
    loop = IngestServeLoop(sb, SPEC, plan=plan, n_components=4, hub=hub)
    q = rng.normal(size=(B, 6, d))            # a NumPy pool, as served
    jax.clear_caches()                        # so the warm query is cold
    loop.query(q)                             # warm: the one compile
    warm = hub.scrape()
    keys = ('jax_traces_total{span="kpca.query"}',
            'jax_compiles_total{span="kpca.query"}')
    assert all(warm.get(k, 0.0) >= 1.0 for k in keys)

    answers = []
    for step in range(3):
        if step:
            loop.ingest(rng.normal(size=(B, d)))     # publishes
        y = loop.query(q)
        head = jax.vmap(lambda s, x: serving.query(s, x, spec=SPEC,
                                                   plan=plan))(loop.snaps, q)
        assert _bits_equal(y, head)
        assert _bits_equal(y, sb.transform(q, n_components=4))
        answers.append(np.asarray(y))
    assert loop.generation == 2
    assert not _bits_equal(answers[0], answers[2])
    after = hub.scrape()
    assert [after.get(k, 0.0) for k in keys] == [warm[k] for k in keys]
    spans = [r for r in hub.spans if r["name"] == "kpca.query"]
    assert [(r["traces"], r["compiles"]) for r in spans[1:]] == [(0, 0)] * 3


def test_project_vectors_kernel_matches_ref():
    """The rect-pruned Uᵀv projection kernel (interpret mode) matches the
    dense reference on the active block and writes exact zeros beyond it
    (inactive columns are identity, supported on rows >= m)."""
    from repro.kernels.eigvec_update import ops as eops

    rng = np.random.default_rng(5)
    M, m, C = 320, 150, 2
    U = np.eye(M)
    qq, _ = np.linalg.qr(rng.normal(size=(m, m)))
    U[:m, :m] = qq
    U = jnp.asarray(U)
    V = jnp.asarray(rng.normal(size=(M, C))) * (np.arange(M) < m)[:, None]
    ref = np.asarray(eops.project_vectors(U, V, jnp.int32(m), force="ref"))
    ker = np.asarray(eops.project_vectors(U, V, jnp.int32(m),
                                          force="interpret"))
    g_cols = -(-m // 128) * 128              # active column tiles
    assert np.abs(ker[:g_cols] - ref[:g_cols]).max() < 1e-10
    assert (ker[g_cols:] == 0.0).all()
    # Masking contract: rows >= m of v are ignored even if nonzero.
    V_dirty = V.at[m:].set(1.0)
    ker2 = np.asarray(eops.project_vectors(U, V_dirty, jnp.int32(m),
                                           force="interpret"))
    assert np.abs(ker2[:g_cols] - ref[:g_cols]).max() < 1e-10


def test_fused_ingest_kernel_projection_matches_dense():
    """ingest_adjusted (second pair projected through the rect-pruned
    kernel) tracks the dense update_adjusted chain."""
    plan = eng.DEFAULT_PLAN
    rng = np.random.default_rng(6)
    d = 5
    x0 = jnp.asarray(rng.normal(size=(4, d)))
    st_a = inkpca.init_state(x0, 64, SPEC, adjusted=True, dtype=jnp.float64)
    st_b = st_a
    for _ in range(8):
        x = jnp.asarray(rng.normal(size=(d,)))
        st_a = inkpca.ingest_adjusted(st_a, x, spec=SPEC, plan=plan)
        a, k_new = inkpca._masked_row(st_b, x, SPEC)
        st_b = inkpca.update_adjusted(st_b, a, k_new, x, plan=plan)
    assert float(jnp.abs(st_a.L[:int(st_a.m)]
                         - st_b.L[:int(st_b.m)]).max()) < 1e-9
    q = jnp.asarray(rng.normal(size=(5, d)))
    ya = eng.transform_state(st_a, q, n_components=4, spec=SPEC,
                             adjusted=True)
    yb = eng.transform_state(st_b, q, n_components=4, spec=SPEC,
                             adjusted=True)
    assert float(jnp.abs(ya - yb).max()) < 1e-9


def test_tenant_mesh_builders_multidevice_subprocess():
    """P_t x P_r = 2x2: the tenant-axis pair matches the local fused pair
    per tenant, tenant-sharded queries match query_batch, and the
    row-rebalanced update matches the full-mesh bucketed update on both
    sides of the crossover (sub-mesh and fallback)."""
    script = r"""
import numpy as np, jax, jax.numpy as jnp
jax.config.update("jax_enable_x64", True)
from repro.core import distributed as dist, engine as eng, rankone
from repro.core import kernels_fn as kf, serving
assert jax.device_count() == 4
rng = np.random.default_rng(7)
M, B, d = 32, 4, 5
plan = eng.DEFAULT_PLAN
kw = dict(iters=eng.resolve_iters(plan.iters, jnp.float64),
          method=plan.method, matmul=plan.inner_matmul,
          precise=plan.precise, merge_fallback=plan.merge_fallback)
def make_state(m):
    A = rng.normal(size=(m, m)); K = A @ A.T
    lam, vec = np.linalg.eigh(K)
    L = jnp.full((M,), 2e30).at[:m].set(jnp.asarray(lam))
    U = jnp.eye(M).at[:m, :m].set(jnp.asarray(vec))
    return L, U
mesh2 = dist.make_tenant_mesh(2, 2)
pair2d = dist.make_tenant_update_pair(mesh2, plan=plan)
Ls, Us, v1s, v2s, ms = [], [], [], [], []
for b in range(B):
    m = 10 + b
    L, U = make_state(m)
    v = jnp.asarray(rng.normal(size=(M,))).at[m:].set(0.0)
    w = jnp.asarray(rng.normal(size=(M,))).at[m:].set(0.0)
    Ls.append(L); Us.append(U); v1s.append(v); v2s.append(w); ms.append(m)
S1 = jnp.asarray(rng.uniform(1.0, 2.0, size=(B,)))
mst = jnp.asarray(ms, jnp.int32)
Lo, Uo = pair2d(jnp.stack(Ls), jnp.stack(Us), jnp.stack(v1s), S1,
                jnp.stack(v2s), -S1, mst)
err_pair = 0.0
for b in range(B):
    Lr, Ur = rankone.rank_one_update_pair(Ls[b], Us[b], v1s[b], S1[b],
                                          v2s[b], -S1[b], ms[b], **kw)
    act = jnp.where(jnp.arange(M) < ms[b], 1.0, 0.0)
    Ko = Uo[b] @ jnp.diag(act * Lo[b]) @ Uo[b].T
    Kr = Ur @ jnp.diag(act * Lr) @ Ur.T
    err_pair = max(err_pair, float(jnp.abs(Ko - Kr).max()))
spec = kf.KernelSpec(name="rbf", sigma=2.0)
sb = eng.StreamBatch(jnp.asarray(rng.normal(size=(B, 3, d))), M, spec,
                     plan=plan._replace(serve_components=4), adjusted=True,
                     dtype=jnp.float64)
for _ in range(4):
    sb.update(jnp.asarray(rng.normal(size=(B, d))))
snaps = sb.publish()
q = jnp.asarray(rng.normal(size=(B, 6, d)))
qt = dist.make_tenant_query(mesh2, spec, plan=plan)
err_q = float(jnp.abs(qt(snaps, q)
                      - serving.query_batch(snaps, q, spec=spec,
                                            plan=plan)).max())
from repro.distributed.sharding import make_mesh
mesh1 = make_mesh((4,), ("data",))
bplan = plan._replace(dispatch="bucketed", min_bucket=8)
reb = dist.make_rebalanced_update(mesh1, plan=bplan)
full = dist.make_sharded_update(mesh1, plan=bplan)
errs_reb = []
for m in (5, 30):          # below / above the P_eff crossover
    L, U = make_state(m)
    v = jnp.asarray(rng.normal(size=(M,))).at[m:].set(0.0)
    L1, U1 = reb(L, U, v, jnp.float64(1.3), jnp.int32(m))
    L2, U2 = full(L, U, v, jnp.float64(1.3), jnp.int32(m))
    errs_reb.append(max(float(jnp.abs(L1 - L2).max()),
                        float(jnp.abs(jnp.asarray(U1)
                                      - jnp.asarray(U2)).max())))
print("RESULT:" + str({"err_pair": err_pair, "err_q": err_q,
                       "err_reb_sub": errs_reb[0],
                       "err_reb_full": errs_reb[1]}))
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = (str(Path(__file__).resolve().parent.parent / "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT:")][-1]
    errs = eval(line[len("RESULT:"):])
    assert errs["err_pair"] < 1e-8, errs
    assert errs["err_q"] < 1e-12, errs
    assert errs["err_reb_sub"] < 1e-10, errs
    assert errs["err_reb_full"] < 1e-10, errs
