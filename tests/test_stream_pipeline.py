"""Golden parity for the composed stream-step pipeline (``Engine.step``).

The variant-matrix collapse holds only if every pre-collapse spelling is
a pure re-spelling: the 2×2×2 (window × health × metrics) combinations
must produce BITWISE-identical bundles whether driven through the legacy
``Engine`` methods or directly through ``step``/``step_block``, the
steady-state window block must still compile to ONE scanned dispatch
(zero added dispatches from the composition), and the fully-composed
(guarded + metered + windowed) P=2 sharded block must agree with the
single-device composed pipeline.
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import engine as eng
from repro.core import health as hl
from repro.core import inkpca
from repro.core import kernels_fn as kf
from repro.core import telemetry as tm
from repro.core import window as wnd

SPEC = kf.KernelSpec(name="rbf", sigma=3.0)
W = 8
COMBOS = [(window, health, metrics)
          for window in (None, W)
          for health in (False, True)
          for metrics in (False, True)]


def _leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        bool(jnp.array_equal(x, y, equal_nan=True)) for x, y in zip(la, lb))


def _setup(window, health, metrics):
    """Engine + initial (legacy-track pieces, bundle) for one combo."""
    rng = np.random.default_rng(13)
    X = jnp.asarray(rng.normal(size=(24, 4)))
    plan = eng.UpdatePlan(dispatch="bucketed", min_bucket=8,
                          health=hl.DEFAULT_POLICY if health else None,
                          window=window, metrics=metrics)
    engine = eng.Engine(SPEC, plan, adjusted=True)
    if window is not None:
        state = wnd.init_window(X[:4], 16, SPEC, adjusted=True,
                                dtype=jnp.float64)
    else:
        # append-only: room for the 4 seeds plus all 14 offered points
        state = inkpca.init_state(X[:4], 32, SPEC, adjusted=True,
                                  dtype=jnp.float64)
    h = hl.init_health(jnp.float64) if health else None
    ms = tm.init_metrics(jnp.float64) if metrics else None
    xs = np.asarray(rng.normal(size=(14, 4)))
    if health:
        xs[3] = np.nan          # growth-phase reject
        xs[9] = np.nan          # steady-state reject (window combos)
    return engine, state, h, ms, jnp.asarray(xs)


@pytest.mark.parametrize("window,health,metrics", COMBOS)
def test_step_parity_with_legacy_point_spellings(window, health, metrics):
    """Point-wise: each legacy spelling and the composed ``step`` advance
    bitwise-identical bundles at EVERY offered point (growth, the
    growth→steady transition, steady state, rejections)."""
    engine, state, h, ms, xs = _setup(window, health, metrics)
    stream = eng.make_stream(state, health=h, metrics=ms)
    for t in range(xs.shape[0]):
        x = xs[t]
        # legacy track
        if window is None:
            if health and metrics:
                state, h, ms = engine.update_guarded_metered(state, h, ms, x)
            elif health:
                state, h = engine.update_guarded(state, h, x)
            elif metrics:
                state, ms = engine.update_metered(state, ms, x)
            else:
                state = engine.update(state, x)
        else:
            if health and metrics:
                state, h, ms = engine.window_ingest_guarded_metered(
                    state, h, ms, x, window=W)
            elif health:
                state, h = engine.window_ingest_guarded(state, h, x,
                                                        window=W)
            elif metrics:
                # pre-collapse KPCAStream spelling: unguarded ingest +
                # clock-delta note
                m0, c0 = state.kpca.m, state.clock
                state = wnd.ingest(engine, state, x, window=W)
                ms = tm.note_block(ms, m0, state.kpca.m, 1,
                                   state.clock - c0, None, window=W)
            else:
                state = wnd.ingest(engine, state, x, window=W)
        # composed track
        stream = engine.step(stream, x, window=window)
        assert _leaves_equal(stream, eng.make_stream(state, health=h,
                                                     metrics=ms))


@pytest.mark.parametrize("window,health,metrics", COMBOS)
def test_step_block_parity_with_legacy_block_spellings(window, health,
                                                       metrics):
    """Block-wise: legacy block spellings and ``step_block`` agree
    bitwise across a growth→steady block and a pure steady block."""
    engine, state, h, ms, xs = _setup(window, health, metrics)
    stream = eng.make_stream(state, health=h, metrics=ms)
    for lo, hi in ((0, 9), (9, 14)):    # transition block, steady block
        blk = xs[lo:hi]
        if window is None:
            if health and metrics:
                state, h, ms = engine.update_block_guarded_metered(
                    state, h, ms, blk)
            elif health:
                state, h = engine.update_block_guarded(state, h, blk)
            elif metrics:
                state, ms = engine.update_block_metered(state, ms, blk)
            else:
                state = engine.update_block(state, blk)
        else:
            if health and metrics:
                state, h, ms = engine.window_block_guarded_metered(
                    state, h, ms, blk, window=W)
            elif health:
                state, h = engine.window_block_guarded(state, h, blk,
                                                       window=W)
            elif metrics:
                state, ms = engine.window_block_metered(state, ms, blk,
                                                        window=W)
            else:
                state = engine.window_block(state, blk, window=W)
        stream = engine.step_block(stream, blk, window=window)
        assert _leaves_equal(stream, eng.make_stream(state, health=h,
                                                     metrics=ms))


def test_bundle_treestructure_is_plan_static():
    """Absent members stay ``None`` leaves through the pipeline, so the
    bundle's treedef — and with it every jit cache key — is a pure
    function of the plan, never of stream history."""
    for window, health, metrics in COMBOS:
        engine, state, h, ms, xs = _setup(window, health, metrics)
        s0 = eng.make_stream(state, health=h, metrics=ms)
        s1 = engine.step(s0, xs[0], window=window)
        s2 = engine.step_block(s1, xs[1:5], window=window)
        assert jax.tree.structure(s0) == jax.tree.structure(s1) \
            == jax.tree.structure(s2)
        assert s2.windowed == (window is not None)
        assert (s2.health is None) == (not health)
        assert (s2.metrics is None) == (not metrics)


def test_step_block_single_dispatch_at_steady_state(monkeypatch):
    """The composed pipeline adds ZERO dispatches to the steady-state
    window scan: one ``_window_scan_chunk`` call per block (unguarded
    bundle), one ``_guarded_window_chunk_impl`` per block (guarded
    bundle), no point-path fallbacks, plus one note dispatch when the
    bundle is metered."""
    rng = np.random.default_rng(23)
    X = jnp.asarray(rng.normal(size=(30, 3)))
    engine = eng.Engine(SPEC, eng.UpdatePlan(dispatch="bucketed",
                                             min_bucket=8, window=W,
                                             health=hl.DEFAULT_POLICY),
                        adjusted=True)
    ws = wnd.init_window(X[:4], 16, SPEC, adjusted=True, dtype=jnp.float64)
    stream = eng.make_stream(ws, health=hl.init_health(jnp.float64),
                             metrics=tm.init_metrics(jnp.float64))
    stream = engine.step_block(stream, X[4:12])      # fill the window
    assert int(stream.kpca.m) == W
    calls = {"scan": 0, "guarded_scan": 0, "point": 0, "note": 0}
    real_scan = eng._window_scan_chunk
    real_guarded = hl._guarded_window_chunk_impl
    real_point = engine._window_point
    real_note = tm.note_block

    def count(key, fn):
        def wrapper(*a, **k):
            calls[key] += 1
            return fn(*a, **k)
        return wrapper

    monkeypatch.setattr(eng, "_window_scan_chunk", count("scan", real_scan))
    monkeypatch.setattr(hl, "_guarded_window_chunk_impl",
                        count("guarded_scan", real_guarded))
    monkeypatch.setattr(engine, "_window_point", count("point", real_point))
    monkeypatch.setattr(tm, "note_block", count("note", real_note))
    stream = engine.step_block(stream, X[12:30])     # 18 steady-state steps
    assert calls == {"scan": 0, "guarded_scan": 1, "point": 0, "note": 1}
    assert int(stream.kpca.m) == W

    # unguarded bundle: the plain scan, once, nothing else
    engine2 = eng.Engine(SPEC, eng.UpdatePlan(dispatch="bucketed",
                                              min_bucket=8, window=W),
                         adjusted=True)
    ws2 = wnd.init_window(X[:4], 16, SPEC, adjusted=True, dtype=jnp.float64)
    s2 = engine2.step_block(eng.make_stream(ws2), X[4:12])
    calls.update(scan=0, guarded_scan=0, point=0, note=0)
    monkeypatch.setattr(engine2, "_window_point",
                        count("point", engine2._window_point))
    engine2.step_block(s2, X[12:30])
    assert calls == {"scan": 1, "guarded_scan": 0, "point": 0, "note": 0}


def test_streambatch_composed_metrics_bitwise():
    """Guarded+metered+windowed StreamBatch lanes are bitwise equal to a
    metrics-off batch — the multi-tenant path rides the same shared
    ``_window_pair`` stage the single-stream scan folds."""
    rng = np.random.default_rng(29)
    B, d = 2, 4
    x0 = jnp.asarray(rng.normal(size=(B, 4, d)))
    steps = [jnp.asarray(rng.normal(size=(B, d))) for _ in range(10)]
    bad = np.array(steps[6])
    bad[0] = np.nan
    steps[6] = jnp.asarray(bad)
    batches = []
    for metrics in (False, True):
        plan = eng.UpdatePlan(health=hl.DEFAULT_POLICY, metrics=metrics,
                              dispatch="bucketed", min_bucket=8)
        b = eng.StreamBatch(x0, 16, SPEC, plan=plan, dtype=jnp.float64,
                            cohorts="bucket", window=W)
        for xs in steps[:6]:
            b.update(xs)
        b.update_block(jnp.stack(steps[6:]))
        batches.append(b)
    off, on = batches
    off._flush(), on._flush()
    assert _leaves_equal(off._full, on._full)
    rep = on.metrics_report()
    np.testing.assert_array_equal(rep["rejections"], [1, 0])
    np.testing.assert_array_equal(rep["ingests"], [9, 10])


@pytest.mark.slow
def test_fully_composed_sharded_block_matches_local_subprocess():
    """P=2: the fully-composed (guarded + metered + windowed) sharded
    block — quarantine gate, FIFO evict, ±sigma pair, note — is bitwise
    equal to the plain sharded builder plus a manual note, and tracks the
    single-device composed ``step_block`` pipeline (same ring/clock/
    counters exactly, eigensystem to collective-reduction tolerance)."""
    script = r"""
import numpy as np, jax, jax.numpy as jnp
jax.config.update("jax_enable_x64", True)
from repro.core import distributed as dkpca, engine as eng, health as hl, \
    inkpca, kernels_fn as kf, telemetry as tm, window as wnd
assert jax.device_count() == 2
SPEC = kf.KernelSpec(name="rbf", sigma=3.0)
rng = np.random.default_rng(31)
X = rng.normal(size=(12, 4))
W = 8
stream = inkpca.KPCAStream(jnp.asarray(X[:4]), 16, SPEC, adjusted=False,
                           dtype=jnp.float64, window=W)
for i in range(4, 12):
    stream.update(jnp.asarray(X[i]))
ws = stream.state
xs = np.asarray(rng.normal(size=(6, 4)))
xs[2] = np.nan
xs = jnp.asarray(xs)
from repro.distributed.sharding import make_mesh
mesh = make_mesh((2,), ("data",))
plan = eng.UpdatePlan(health=hl.DEFAULT_POLICY)
wb = dkpca.make_sharded_window_block(mesh, SPEC, plan=plan)
wbm = dkpca.make_sharded_window_block_metered(mesh, SPEC, plan=plan)
args = (ws.kpca.L, ws.kpca.U, ws.kpca.X, ws.ages, ws.clock, xs, ws.kpca.m)
plain = wb(*args)
metered = wbm(*args, tm.init_metrics(jnp.float64))
bitwise = all(bool(jnp.array_equal(a, b)) for a, b in zip(plain, metered[:5]))
rep = tm.metrics_report(metered[5])
# single-device composed pipeline on the same inputs
engine = eng.Engine(SPEC, plan, adjusted=False)
bundle = eng.make_stream(ws, health=hl.init_health(jnp.float64),
                         metrics=tm.init_metrics(jnp.float64))
out = engine.step_block(bundle, xs, window=W)
lrep = tm.metrics_report(out.metrics)
err_L = float(jnp.max(jnp.abs(metered[0][:W] - out.kpca.L[:W])))
ring_equal = bool(jnp.array_equal(metered[3], out.ages)) \
    and int(metered[4]) == int(out.clock)
print("RESULT:" + str({
    "bitwise": bitwise, "ring_equal": ring_equal, "err_L": err_L < 1e-8,
    "ingests": rep["ingests"], "rejections": rep["rejections"],
    "local_ingests": lrep["ingests"], "local_rejections": lrep["rejections"],
    "evictions": rep["evictions"], "fill": rep["window_fill"]}))
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
    assert res == {"bitwise": True, "ring_equal": True, "err_L": True,
                   "ingests": 5, "rejections": 1, "local_ingests": 5,
                   "local_rejections": 1, "evictions": 5, "fill": 1.0}
