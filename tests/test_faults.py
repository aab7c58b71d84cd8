"""Fault-injection harness + crash-safety of the checkpoint store.

Kill-mid-save: ``npz_store.save_checkpoint`` embeds named killpoints at
every instant a real process can die.  Arming each one in turn simulates
a kill -9 at exactly that line; after every simulated crash the store's
``latest_step`` must still point at an INTACT, loadable checkpoint, and
the next successful save must leave no debris.

The P=2 subprocess test drives the sharded fused window path with a NaN
arrival: the quarantine verdict is computed from the replicated point, so
every shard rejects identically, the collective schedule never diverges,
and the final state is bitwise the one of a stream that never saw the
point.
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.testing import faults

KILLPOINTS = ("checkpoint.mid_write", "checkpoint.after_write",
              "checkpoint.between_renames", "checkpoint.after_publish")


# ------------------------------------------------------------ harness --
def test_trip_is_noop_unless_armed():
    faults.trip("never.armed")          # must not raise
    assert not faults.armed("some.point")


def test_arm_trip_disarm_cycle():
    faults.arm("p1")
    assert faults.armed("p1")
    with pytest.raises(faults.FaultInjected) as ei:
        faults.trip("p1")
    assert ei.value.point == "p1"
    assert not faults.armed("p1")       # auto-disarmed on fire
    faults.trip("p1")                   # now a no-op again


def test_arm_after_skips_n_hits():
    faults.arm("p2", after=2)
    faults.trip("p2")
    faults.trip("p2")
    with pytest.raises(faults.FaultInjected):
        faults.trip("p2")


def test_injected_contextmanager_disarms():
    with pytest.raises(faults.FaultInjected):
        with faults.injected("p3"):
            faults.trip("p3")
    assert not faults.armed("p3")
    with faults.injected("p4"):
        pass
    assert not faults.armed("p4")


def test_fault_injected_not_caught_by_except_exception():
    faults.arm("p5")
    with pytest.raises(faults.FaultInjected):
        try:
            faults.trip("p5")
        except Exception:               # a recovery block must NOT eat it
            pytest.fail("FaultInjected was swallowed by except Exception")


# --------------------------------------------------------- corruptors --
def test_nan_point_kinds():
    for kind, val in (("nan", np.nan), ("inf", np.inf), ("-inf", -np.inf)):
        x = faults.nan_point(5, kind=kind, index=2)
        assert x.shape == (5,)
        if kind == "nan":
            assert np.isnan(x[2])
        else:
            assert x[2] == val
    base = np.arange(4.0)
    x = faults.nan_point(4, base=base, index=1)
    assert np.isnan(x[1]) and x[0] == 0.0 and x[3] == 3.0
    assert base[1] == 1.0               # base not mutated


def _state(dtype=jnp.float64):
    from repro.core import inkpca, kernels_fn as kf

    rng = np.random.default_rng(0)
    spec = kf.KernelSpec(name="rbf", sigma=2.0)
    return inkpca.init_state(jnp.asarray(rng.normal(size=(6, 3)), dtype),
                             8, spec, adjusted=True, dtype=dtype)


def test_bitflip_eigvec():
    st = _state()
    flipped = faults.bitflip_eigvec(st, 1, 2, bit=63)   # f64 sign bit
    U0, U1 = np.asarray(st.U), np.array(flipped.U)
    assert U1[1, 2] == -U0[1, 2]
    U1[1, 2] = U0[1, 2]
    np.testing.assert_array_equal(U0, U1)


def test_corrupt_eigvecs_touches_only_active_block():
    st = _state()
    bad = faults.corrupt_eigvecs(st, magnitude=0.1, seed=1)
    m = int(st.m)
    np.testing.assert_array_equal(np.asarray(bad.U[m:, :]),
                                  np.asarray(st.U[m:, :]))
    np.testing.assert_array_equal(np.asarray(bad.U[:, m:]),
                                  np.asarray(st.U[:, m:]))
    assert float(jnp.abs(bad.U - st.U).max()) > 0


def test_corrupt_eigenvalue_and_poison_row():
    st = _state()
    assert float(faults.corrupt_eigenvalue(st, 0, value=-2.0).L[0]) == -2.0
    assert np.isnan(np.asarray(faults.poison_stored_row(st, 1).X[1])).all()


# ------------------------------------------------------ kill-mid-save --
def _tree(step):
    return {"w": jnp.arange(6, dtype=jnp.float32) + step,
            "step": jnp.asarray(step, jnp.int32)}


def _shapes():
    return jax.eval_shape(lambda: _tree(0))


@pytest.mark.parametrize("point", KILLPOINTS)
def test_kill_mid_save_fresh_step(tmp_path, point):
    """Crash while writing step 2 (step 1 already on disk): latest_step
    must keep serving an intact checkpoint — step 1 for every pre-publish
    crash, step 2 once the publish rename happened."""
    d = str(tmp_path)
    save_checkpoint(d, 1, _tree(1))
    try:
        with faults.injected(point):
            save_checkpoint(d, 2, _tree(2))
        crashed = False
    except faults.FaultInjected:
        crashed = True
    # between_renames never trips for a FRESH step (no aside to rename);
    # after_publish trips after the checkpoint is already live.
    assert crashed == (point != "checkpoint.between_renames")
    step = latest_step(d)
    assert step in (1, 2)
    out = load_checkpoint(d, step, _shapes())
    assert int(out["step"]) == step
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.arange(6, dtype=np.float32) + step)
    if point in ("checkpoint.mid_write", "checkpoint.after_write"):
        assert step == 1                # crash before publish: old survives
    if point == "checkpoint.after_publish":
        assert step == 2                # publish completed before the kill


@pytest.mark.parametrize("point", KILLPOINTS)
def test_kill_mid_overwrite_same_step(tmp_path, point):
    """Crash while OVERWRITING an existing step: either the old or the
    new content must load — never a torn directory."""
    d = str(tmp_path)
    save_checkpoint(d, 3, _tree(3))
    save_checkpoint(d, 7, _tree(7))
    try:
        with faults.injected(point):
            save_checkpoint(d, 7, {"w": jnp.full((6,), -1.0, jnp.float32),
                                   "step": jnp.asarray(7, jnp.int32)})
    except faults.FaultInjected:
        pass
    step = latest_step(d)
    assert step in (3, 7)
    out = load_checkpoint(d, step, _shapes())
    w = np.asarray(out["w"])
    assert (np.array_equal(w, np.arange(6, dtype=np.float32) + step)
            or np.array_equal(w, np.full((6,), -1.0, np.float32)))


def test_recovery_save_cleans_debris(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, 1, _tree(1))
    for point in KILLPOINTS:
        try:
            with faults.injected(point):
                save_checkpoint(d, 2, _tree(2))
        except faults.FaultInjected:
            pass
    save_checkpoint(d, 3, _tree(3))
    names = os.listdir(d)
    assert all(".tmp-" not in n for n in names), names
    assert latest_step(d) == 3


# --------------------------------------- P=2 sharded NaN quarantine ---
def test_sharded_quarantine_multidevice_subprocess():
    """P=2: a NaN arrival on the sharded fused-window path is rejected
    identically on both shards (replicated verdict, fixed collective
    schedule — no divergence/deadlock) and the final state is bitwise the
    clean stream's; the quarantine count is recoverable from the clock."""
    script = r"""
import numpy as np, jax, jax.numpy as jnp
jax.config.update("jax_enable_x64", True)
from repro.core import distributed as dkpca, engine as eng, health as hl, \
    inkpca, kernels_fn as kf
from repro.testing import faults
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
clean = jnp.asarray(rng.normal(size=(5, 4)))
bad = np.array(clean)
bad = np.insert(bad, 2, faults.nan_point(4).astype(np.float64), axis=0)
from repro.distributed.sharding import make_mesh
mesh = make_mesh((2,), ("data",))
plan = eng.UpdatePlan(fuse_krow=True, matmul="jnp2",
                      health=hl.DEFAULT_POLICY)
wb = dkpca.make_sharded_window_block(mesh, SPEC, plan=plan)
Lb, Ub, Xb, agesb, clockb = wb(ws.kpca.L, ws.kpca.U, ws.kpca.X, ws.ages,
                               ws.clock, jnp.asarray(bad), ws.kpca.m)
Lc, Uc, Xc, agesc, clockc = wb(ws.kpca.L, ws.kpca.U, ws.kpca.X, ws.ages,
                               ws.clock, clean, ws.kpca.m)
same = all(bool(jnp.array_equal(a, b)) for a, b in
           ((Lb, Lc), (Ub, Uc), (Xb, Xc), (agesb, agesc)))
quarantined = int(bad.shape[0] - (clockb - ws.clock))
print("RESULT:" + str({"bitwise": same, "quarantined": quarantined,
                       "clock_matches": int(clockb) == int(clockc)}))
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
    res = eval(line[len("RESULT:"):])
    assert res == {"bitwise": True, "quarantined": 1, "clock_matches": True}
