"""Sharding rules, HLO parser, straggler monitor, distributed KPCA."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from repro.distributed.straggler import HeartbeatMonitor, StepTimer
from repro.launch import hlo_parse
from repro.distributed.sharding import make_mesh


# ------------------------------------------------------------- sharding ----
def test_logical_to_spec_divisibility_drop():
    mesh = make_mesh((1,), ("model",))
    with shd.use_mesh(mesh, rules={"heads": "model", "batch": "data"}):
        # 'data' axis absent from mesh -> dropped by use_mesh filtering
        spec = shd.logical_to_spec(("batch", "heads"), (4, 8))
        assert spec == P(None, "model")


def test_logical_to_spec_dedup_axes():
    mesh = make_mesh((1,), ("model",))
    with shd.use_mesh(mesh, rules={"a": "model", "b": "model"}):
        spec = shd.logical_to_spec(("a", "b"), (4, 4))
        assert spec == P("model", None)   # first dim wins


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = shd.constrain(x, ("batch", None))
    assert y is x


def test_use_mesh_restores_state():
    mesh = make_mesh((1,), ("data",))
    assert shd.get_mesh() is None
    with shd.use_mesh(mesh):
        assert shd.get_mesh() is mesh
    assert shd.get_mesh() is None


# ------------------------------------------------------------ hlo parser ---
def test_hlo_parse_counts_real_matmul_flops():
    m, k, n = 64, 32, 48

    def f(a, b):
        return a @ b

    hlo = (jax.jit(f)
           .lower(jnp.zeros((m, k)), jnp.zeros((k, n))).compile().as_text())
    stats = hlo_parse.analyze(hlo)
    expect = 2.0 * m * k * n
    assert stats.flops == expect, (stats.flops, expect)


def test_hlo_parse_scan_trip_multiplication():
    def f(x):
        def body(c, _):
            return c @ c, None
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out

    hlo = jax.jit(f).lower(jnp.zeros((16, 16))).compile().as_text()
    stats = hlo_parse.analyze(hlo)
    assert stats.flops == 7 * 2.0 * 16 ** 3, stats.flops


def test_hlo_parse_bytes_reasonable():
    n = 256

    def f(a, b):
        return a @ b

    hlo = (jax.jit(f)
           .lower(jnp.zeros((n, n), jnp.float32),
                  jnp.zeros((n, n), jnp.float32)).compile().as_text())
    stats = hlo_parse.analyze(hlo)
    raw = 3 * n * n * 4
    assert raw <= stats.bytes <= 5 * raw


def test_collective_wire_model():
    line = ("  %all-reduce.1 = f32[1024]{0} all-reduce(%x), "
            "replica_groups={{0,1,2,3}}, to_apply=%add")
    comps = {"c": hlo_parse.Computation(name="c")}
    op = hlo_parse.Op(name="all-reduce.1", opcode="all-reduce",
                      result_bytes=4096.0, line=line,
                      result_seg="f32[1024]{0}")
    kind, wire, payload = hlo_parse._collective_wire(op)
    assert kind == "all-reduce"
    assert wire == 2.0 * 3 / 4 * 4096
    assert payload == 4096


# ------------------------------------------------------------- straggler ---
def test_heartbeat_flags_timeout():
    hb = HeartbeatMonitor(n_workers=2, timeout_s=10.0)
    hb.beat(0, step=5, t=100.0)
    hb.beat(1, step=5, t=100.0)
    assert hb.healthy(now=105.0)
    flagged = hb.flagged(now=150.0)
    assert len(flagged) == 2 and flagged[0]["reason"] == "timeout"


def test_heartbeat_flags_lag():
    hb = HeartbeatMonitor(n_workers=3, timeout_s=1e9, max_step_lag=5)
    hb.beat(0, step=100, t=0.0)
    hb.beat(1, step=100, t=0.0)
    hb.beat(2, step=50, t=0.0)
    flagged = hb.flagged(now=1.0)
    assert [f["worker"] for f in flagged] == [2]
    assert flagged[0]["reason"] == "lagging"


def test_heartbeat_never_beat():
    hb = HeartbeatMonitor(n_workers=2)
    hb.beat(0, step=1)
    assert any(f["reason"] == "never-beat" for f in hb.flagged())


def test_step_timer_spike_detection():
    st = StepTimer(alpha=0.5, spike_factor=2.0)
    st.ewma = 1.0
    st._t0 = 0.0
    import time as _t
    real = _t.time
    try:
        _t.time = lambda: 10.0   # 10s step vs 1s ewma -> spike
        st.stop()
    finally:
        _t.time = real
    assert st.spikes == 1


# ---------------------------------------------------- distributed KPCA -----
def test_sharded_rank_one_update_matches_local():
    from repro.core import distributed as dkpca, rankone

    rng = np.random.default_rng(7)
    m, M = 10, 16
    A = rng.normal(size=(m, m)); A = A @ A.T
    lam, vec = np.linalg.eigh(A)
    L = np.zeros(M); U = np.eye(M)
    L[:m] = lam; U[:m, :m] = vec
    L = rankone.sentinelize(jnp.asarray(L), jnp.int32(m), jnp.float64(0.0))
    v = np.zeros(M); v[:m] = rng.normal(size=m)

    mesh = make_mesh((1,), ("data",))
    upd = dkpca.make_sharded_update(mesh)
    Ls, Us = upd(jnp.asarray(L), jnp.asarray(U), jnp.asarray(v),
                 jnp.float64(1.7), jnp.int32(m))
    Ll, Ul = rankone.rank_one_update(jnp.asarray(L), jnp.asarray(U),
                                     jnp.asarray(v), jnp.float64(1.7),
                                     jnp.int32(m))
    np.testing.assert_allclose(np.asarray(Ls), np.asarray(Ll), atol=1e-10)
    np.testing.assert_allclose(np.abs(np.asarray(Us)),
                               np.abs(np.asarray(Ul)), atol=1e-8)


def test_sharded_pair_update_matches_local_pair():
    """Fused ±sigma pair under shard_map (one psum for both z vectors) must
    match the local fused pair; plans come from the engine layer."""
    from repro.core import distributed as dkpca, engine as eng, rankone

    rng = np.random.default_rng(8)
    m, M = 10, 16
    A = rng.normal(size=(m, m)); A = A @ A.T
    lam, vec = np.linalg.eigh(A)
    L = np.zeros(M); U = np.eye(M)
    L[:m] = lam; U[:m, :m] = vec
    L = rankone.sentinelize(jnp.asarray(L), jnp.int32(m), jnp.float64(0.0))
    v1 = np.zeros(M); v1[:m] = rng.normal(size=m)
    v2 = np.zeros(M); v2[:m] = rng.normal(size=m)

    mesh = make_mesh((1,), ("data",))
    pair = dkpca.make_sharded_update_pair(mesh, plan=eng.UpdatePlan())
    Ls, Us = pair(jnp.asarray(L), jnp.asarray(U), jnp.asarray(v1),
                  jnp.float64(1.7), jnp.asarray(v2), jnp.float64(-1.7),
                  jnp.int32(m))
    Ll, Ul = rankone.rank_one_update_pair(
        jnp.asarray(L), jnp.asarray(U), jnp.asarray(v1), jnp.float64(1.7),
        jnp.asarray(v2), jnp.float64(-1.7), jnp.int32(m), precise=False,
        merge_fallback=False)
    np.testing.assert_allclose(np.asarray(Ls), np.asarray(Ll), atol=1e-10)
    np.testing.assert_allclose(np.abs(np.asarray(Us)),
                               np.abs(np.asarray(Ul)), atol=1e-8)
    # and against two sequential local updates (end-to-end semantics)
    L2, U2 = rankone.rank_one_update(jnp.asarray(L), jnp.asarray(U),
                                     jnp.asarray(v1), jnp.float64(1.7),
                                     jnp.int32(m))
    L2, U2 = rankone.rank_one_update(L2, U2, jnp.asarray(v2),
                                     jnp.float64(-1.7), jnp.int32(m))
    np.testing.assert_allclose(np.asarray(Ls[:m]), np.asarray(L2[:m]),
                               atol=1e-8)


def _clustered_state(rng, m, M):
    """Spectrum with a tight eigenvalue cluster so the dlaed2 merge fires."""
    from repro.core import rankone
    lam = np.sort(rng.uniform(1.0, 5.0, size=m))
    lam[3:7] = lam[3]            # exactly-degenerate run
    q, _ = np.linalg.qr(rng.normal(size=(m, m)))
    L = np.zeros(M); U = np.eye(M)
    L[:m] = lam; U[:m, :m] = q
    L = rankone.sentinelize(jnp.asarray(L), jnp.int32(m), jnp.float64(0.0))
    return L, jnp.asarray(U)


def test_sharded_pair_fallback_matches_two_single_updates_clustered():
    """On a clustered spectrum the collective-balanced merge fallback must
    route the sharded fused pair through the sequential pipeline — landing
    exactly on two single sharded updates."""
    from repro.core import distributed as dkpca, engine as eng, rankone

    rng = np.random.default_rng(9)
    m, M = 12, 16
    L, U = _clustered_state(rng, m, M)
    v1 = np.zeros(M); v1[:m] = rng.normal(size=m)
    v2 = np.zeros(M); v2[:m] = rng.normal(size=m)
    # the scenario actually exercises the fallback branch
    assert bool(rankone._merge_fires(L, U.T @ jnp.asarray(v1),
                                     jnp.float64(1.7), jnp.int32(m)))

    mesh = make_mesh((1,), ("data",))
    pair = dkpca.make_sharded_update_pair(
        mesh, plan=eng.UpdatePlan(merge_fallback=True))
    Lp, Up = pair(L, U, jnp.asarray(v1), jnp.float64(1.7), jnp.asarray(v2),
                  jnp.float64(-1.7), jnp.int32(m))
    upd = dkpca.make_sharded_update(mesh)
    Ls, Us = upd(L, U, jnp.asarray(v1), jnp.float64(1.7), jnp.int32(m))
    Ls, Us = upd(Ls, Us, jnp.asarray(v2), jnp.float64(-1.7), jnp.int32(m))
    np.testing.assert_allclose(np.asarray(Lp), np.asarray(Ls), atol=1e-10)
    np.testing.assert_allclose(np.abs(np.asarray(Up)),
                               np.abs(np.asarray(Us)), atol=1e-8)
    # orthogonality is what the fallback buys on clustered spectra
    orth = np.abs(np.asarray(Up[:m, :m]) @ np.asarray(Up[:m, :m]).T
                  - np.eye(m)).max()
    assert orth < 1e-10, orth


def test_sharded_bucketed_update_matches_local():
    """Bucketed sharded dispatch (rectangular local slices) must equal the
    full-capacity local update while m < M_b."""
    from repro.core import distributed as dkpca, engine as eng, rankone

    rng = np.random.default_rng(10)
    m, M = 10, 64
    A = rng.normal(size=(m, m)); A = A @ A.T
    lam, vec = np.linalg.eigh(A)
    L = np.zeros(M); U = np.eye(M)
    L[:m] = lam; U[:m, :m] = vec
    L = rankone.sentinelize(jnp.asarray(L), jnp.int32(m), jnp.float64(0.0))
    U = jnp.asarray(U)
    v = np.zeros(M); v[:m] = rng.normal(size=m)
    v = jnp.asarray(v)

    mesh = make_mesh((1,), ("data",))
    upd = dkpca.make_sharded_update(
        mesh, plan=eng.UpdatePlan(dispatch="bucketed", min_bucket=16))
    Ls, Us = upd(L, U, v, jnp.float64(1.7), jnp.int32(m))
    Ll, Ul = rankone.rank_one_update(L, U, v, jnp.float64(1.7),
                                     jnp.int32(m))
    # active spectrum + reconstruction (sentinel tails are bookkeeping and
    # legitimately differ between the bucketed and fixed paths)
    np.testing.assert_allclose(np.asarray(Ls[:m]), np.asarray(Ll[:m]),
                               atol=1e-10)
    np.testing.assert_allclose(
        np.asarray(rankone.reconstruct(Ls, Us, jnp.int32(m))),
        np.asarray(rankone.reconstruct(Ll, Ul, jnp.int32(m))), atol=1e-8)

    pairb = dkpca.make_sharded_update_pair(
        mesh, plan=eng.UpdatePlan(dispatch="bucketed", min_bucket=16,
                                  merge_fallback=False))
    v2 = np.zeros(M); v2[:m] = rng.normal(size=m)
    Lp, Up = pairb(L, U, v, jnp.float64(1.7), jnp.asarray(v2),
                   jnp.float64(-1.7), jnp.int32(m))
    Lr, Ur = rankone.rank_one_update_pair(
        L, U, v, jnp.float64(1.7), jnp.asarray(v2), jnp.float64(-1.7),
        jnp.int32(m), merge_fallback=False)
    np.testing.assert_allclose(np.asarray(Lp[:m]), np.asarray(Lr[:m]),
                               atol=1e-10)
    np.testing.assert_allclose(
        np.asarray(rankone.reconstruct(Lp, Up, jnp.int32(m))),
        np.asarray(rankone.reconstruct(Lr, Ur, jnp.int32(m))), atol=1e-8)


def test_sharded_rect_pruning_multidevice_subprocess():
    """P=2 end-to-end: the bucketed rectangular path on a REAL two-device
    mesh (host-device override needs a fresh process) must match the local
    update, fused pair fallback included."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    script = r"""
import numpy as np, jax, jax.numpy as jnp
jax.config.update("jax_enable_x64", True)
from repro.core import distributed as dkpca, engine as eng, rankone
assert jax.device_count() == 2
rng = np.random.default_rng(12)
m, M = 10, 32
A = rng.normal(size=(m, m)); A = A @ A.T
lam, vec = np.linalg.eigh(A)
L = np.zeros(M); U = np.eye(M)
L[:m] = lam; U[:m, :m] = vec
L = rankone.sentinelize(jnp.asarray(L), jnp.int32(m), jnp.float64(0.0))
U = jnp.asarray(U)
v1 = np.zeros(M); v1[:m] = rng.normal(size=m)
v2 = np.zeros(M); v2[:m] = rng.normal(size=m)
v1, v2 = jnp.asarray(v1), jnp.asarray(v2)
from repro.distributed.sharding import make_mesh
mesh = make_mesh((2,), ("data",))
upd = dkpca.make_sharded_update(
    mesh, plan=eng.UpdatePlan(dispatch="bucketed", min_bucket=16))
Ls, Us = upd(L, U, v1, jnp.float64(1.7), jnp.int32(m))
Ll, Ul = rankone.rank_one_update(L, U, v1, jnp.float64(1.7), jnp.int32(m))
pair = dkpca.make_sharded_update_pair(
    mesh, plan=eng.UpdatePlan(dispatch="bucketed", min_bucket=16,
                              merge_fallback=True))
Lp, Up = pair(L, U, v1, jnp.float64(1.7), v2, jnp.float64(-1.7),
              jnp.int32(m))
L2, U2 = rankone.rank_one_update(L, U, v1, jnp.float64(1.7), jnp.int32(m))
L2, U2 = rankone.rank_one_update(L2, U2, v2, jnp.float64(-1.7),
                                 jnp.int32(m))
K_s = rankone.reconstruct(Ls, Us, jnp.int32(m))
K_l = rankone.reconstruct(Ll, Ul, jnp.int32(m))
print("RESULT:" + str({
    "err_L": float(jnp.abs(Ls[:m] - Ll[:m]).max()),
    "err_U": float(jnp.abs(K_s - K_l).max()),
    "err_pair_L": float(jnp.abs(Lp[:m] - L2[:m]).max()),
}))
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
    assert errs["err_L"] < 1e-10, errs
    assert errs["err_U"] < 1e-8, errs
    assert errs["err_pair_L"] < 1e-8, errs


def test_sharded_evict_and_window_multidevice_subprocess():
    """P=2 end-to-end: arbitrary-row sharded eviction (in-graph boundary
    permutation) and the scanned sharded window block on a REAL
    two-device mesh must match the local decremental path (ISSUE
    acceptance: sharded arbitrary-row eviction == local downdate)."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    script = r"""
import numpy as np, jax, jax.numpy as jnp
jax.config.update("jax_enable_x64", True)
from repro.core import distributed as dkpca, engine as eng, inkpca, \
    kernels_fn as kf, rankone
assert jax.device_count() == 2
SPEC = kf.KernelSpec(name="rbf", sigma=5.0)
rng = np.random.default_rng(37)
X = rng.normal(size=(12, 4))
engine = eng.Engine(SPEC, eng.UpdatePlan(), adjusted=False)
st = inkpca.init_state(jnp.asarray(X[:4]), 16, SPEC, adjusted=False,
                       dtype=jnp.float64)
for i in range(4, 11):
    st = engine.update(st, jnp.asarray(X[i]))
from repro.distributed.sharding import make_mesh
mesh = make_mesh((2,), ("data",))
errs = {}
ev = dkpca.make_sharded_evict(
    mesh, plan=eng.UpdatePlan(dispatch="bucketed", min_bucket=8))
victim = 3                                         # interior row
a = kf.kernel_row(st.X[victim], st.X, spec=SPEC)
a = jnp.where(rankone.active_mask(16, st.m), a, 0.0)
Ls, Us, ms = ev(st.L, st.U, a, a[victim], jnp.int32(victim), st.m)
ref = engine.downdate(st, victim)
errs["evict_L"] = float(jnp.abs(Ls[:int(ms)] - ref.L[:int(ms)]).max())
errs["evict_K"] = float(jnp.abs(
    rankone.reconstruct(Ls, Us, ms)
    - rankone.reconstruct(ref.L, ref.U, ref.m)).max())
W = 8
stream = inkpca.KPCAStream(jnp.asarray(X[:4]), 16, SPEC, adjusted=False,
                           dtype=jnp.float64, window=W)
for i in range(4, 12):
    stream.update(jnp.asarray(X[i]))
ws = stream.state
xs = jnp.asarray(rng.normal(size=(5, 4)))
wb = dkpca.make_sharded_window_block(
    mesh, SPEC, plan=eng.UpdatePlan(dispatch="bucketed", min_bucket=8))
L2, U2, X2, ages2, clock2 = wb(ws.kpca.L, ws.kpca.U, ws.kpca.X, ws.ages,
                               ws.clock, xs, ws.kpca.m)
for t in range(5):
    stream.update(xs[t])
r = stream.state
errs["win_L"] = float(jnp.abs(L2[:W] - r.kpca.L[:W]).max())
errs["win_K"] = float(jnp.abs(
    rankone.reconstruct(L2, U2, jnp.int32(W))
    - rankone.reconstruct(r.kpca.L, r.kpca.U, r.kpca.m)).max())
errs["win_ages"] = int(jnp.abs(ages2 - r.ages).max())
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
    assert errs["evict_L"] < 1e-10, errs
    assert errs["evict_K"] < 1e-10, errs
    assert errs["win_L"] < 1e-10, errs
    assert errs["win_K"] < 1e-10, errs
    assert errs["win_ages"] == 0, errs
