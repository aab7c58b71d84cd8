"""Unified telemetry layer (``core/telemetry`` + ``repro.obs``).

The two invariants that make metrics free to turn on are locked in here:

* **Bitwise identity** — a stream with ``plan.metrics`` on runs the
  *identical* jitted update callables as one with it off, on every
  dispatch path (plain, guarded, windowed, multi-tenant, P=2 sharded);
  the eigensystem/ring/clock leaves must be bitwise equal.
* **Exact counters** — ingests/rejections/evictions are identities over
  values the updates already produce, checked against a pure-Python
  oracle over a long mixed stream (growth, full-window eviction,
  quarantined NaNs, block and single-point entry points).
"""
import os
import subprocess
import sys
import urllib.request
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import engine as eng
from repro.core import health as hl
from repro.core import inkpca
from repro.core import kernels_fn as kf
from repro.core import telemetry as tm
from repro.testing import faults

SPEC = kf.KernelSpec(name="rbf", sigma=2.0)


def _leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        bool(jnp.array_equal(x, y, equal_nan=True)) for x, y in zip(la, lb))


def _drive(stream, X, poison_at=()):
    """Mixed driver: singles, one block, optional NaN injections."""
    n = X.shape[0]
    for i in range(n // 2):
        x = X[i]
        if i in poison_at:
            x = jnp.asarray(faults.nan_point(X.shape[1]))
        stream.update(x)
    rest = np.array(X[n // 2:])
    for i in poison_at:
        if 0 <= i - n // 2 < rest.shape[0]:
            rest[i - n // 2] = np.nan
    stream.update_block(jnp.asarray(rest))


# ------------------------------------------------- bitwise identity ------
@pytest.mark.parametrize("window", [None, 8])
@pytest.mark.parametrize("health", [False, True])
def test_metrics_on_off_bitwise_single_stream(window, health):
    rng = np.random.default_rng(3)
    X = jnp.asarray(rng.normal(size=(26, 4)))
    policy = hl.DEFAULT_POLICY if health else None
    poison = (7, 15) if health else ()
    streams = []
    for metrics in (False, True):
        plan = eng.UpdatePlan(health=policy, metrics=metrics)
        s = inkpca.KPCAStream(X[:4], 16, SPEC, adjusted=not window,
                              plan=plan, dtype=jnp.float64, window=window)
        _drive(s, X[4:], poison_at=poison)
        streams.append(s)
    off, on = streams
    assert _leaves_equal(off.state, on.state)
    assert off.metrics is None and on.metrics is not None
    rep = on.metrics_report()
    offered = 22
    assert rep["rejections"] == len(poison)
    assert rep["ingests"] == offered - len(poison)
    assert rep["m"] == float(int(on.kpca_state.m))
    if window:
        assert rep["evictions"] == rep["ingests"] - (int(on.kpca_state.m) - 4)
        assert rep["window_fill"] == pytest.approx(
            int(on.kpca_state.m) / window)
    else:
        assert rep["evictions"] == 0
        assert rep["window_fill"] == tm.GAUGE_UNSET


def test_metrics_on_off_bitwise_streambatch():
    rng = np.random.default_rng(4)
    B, d = 3, 4
    x0 = jnp.asarray(rng.normal(size=(B, 4, d)))
    steps = [jnp.asarray(rng.normal(size=(B, d))) for _ in range(12)]
    bad = np.array(steps[5])
    bad[1] = np.nan
    steps[5] = jnp.asarray(bad)
    batches = []
    for metrics in (False, True):
        plan = eng.UpdatePlan(health=hl.DEFAULT_POLICY, metrics=metrics)
        b = eng.StreamBatch(x0, 16, SPEC, plan=plan, dtype=jnp.float64,
                            window=8)
        for xs in steps[:8]:
            b.update(xs)
        b.update_block(jnp.stack(steps[8:]))      # (T, B, d)
        b.publish(4)
        batches.append(b)
    off, on = batches
    off._flush(), on._flush()
    assert _leaves_equal(off._full, on._full)
    rep = on.metrics_report()
    np.testing.assert_array_equal(rep["rejections"], [0, 1, 0])
    np.testing.assert_array_equal(rep["ingests"], [12, 11, 12])
    np.testing.assert_array_equal(rep["publishes"], [1, 1, 1])
    assert rep["ingests_total"] == 35


# ------------------------------------------------- counter exactness -----
def test_counter_oracle_500_step_mixed_stream():
    """500 offered points through a guarded sliding window, counted
    against a pure-Python oracle (NaN every 23rd point, singles and
    blocks interleaved)."""
    rng = np.random.default_rng(5)
    W, d = 12, 3
    plan = eng.UpdatePlan(health=hl.DEFAULT_POLICY, metrics=True)
    s = inkpca.KPCAStream(jnp.asarray(rng.normal(size=(4, d))), 16, SPEC,
                          adjusted=False, plan=plan, dtype=jnp.float64,
                          window=W)
    oracle = {"ingests": 0, "rejections": 0, "evictions": 0, "m": 4}
    offered = 0
    buf = []

    def offer(x):
        nonlocal offered
        offered += 1
        if not np.isfinite(x).all():
            oracle["rejections"] += 1
            return
        oracle["ingests"] += 1
        if oracle["m"] == W:
            oracle["evictions"] += 1
        else:
            oracle["m"] += 1

    while offered < 500:
        x = rng.normal(size=(d,))
        if offered % 23 == 7:
            x = x * np.nan
        offer(x)
        buf.append(x)
        # flush as a block every 9 points, as singles otherwise
        if len(buf) == 9:
            s.update_block(jnp.asarray(np.stack(buf)))
            buf = []
        elif offered % 4 == 0:
            for b in buf:
                s.update(jnp.asarray(b))
            buf = []
    for b in buf:
        s.update(jnp.asarray(b))

    rep = s.metrics_report()
    assert rep["ingests"] == oracle["ingests"]
    assert rep["rejections"] == oracle["rejections"]
    assert rep["evictions"] == oracle["evictions"]
    assert rep["m"] == float(oracle["m"]) == float(int(s.kpca_state.m))
    assert int(s.state.clock) == oracle["ingests"] + 4   # + seed rows


def test_stacked_lanes_match_per_tenant_streams():
    """B metric lanes through the vmapped StreamBatch == B independent
    KPCAStream loops over the same per-tenant points."""
    rng = np.random.default_rng(6)
    B, d, W = 3, 4, 8
    x0 = np.asarray(rng.normal(size=(B, 4, d)))
    steps = np.asarray(rng.normal(size=(14, B, d)))
    steps[4, 2] = np.nan
    steps[9, 0] = np.nan

    plan = eng.UpdatePlan(health=hl.DEFAULT_POLICY, metrics=True)
    batch = eng.StreamBatch(jnp.asarray(x0), 16, SPEC, plan=plan,
                            dtype=jnp.float64, window=W)
    for xs in steps:
        batch.update(jnp.asarray(xs))
    got = batch.metrics_report()

    want = {k: [] for k in ("ingests", "rejections", "evictions", "m")}
    for t in range(B):
        s = inkpca.KPCAStream(jnp.asarray(x0[t]), 16, SPEC, adjusted=False,
                              plan=plan, dtype=jnp.float64, window=W)
        for i in range(steps.shape[0]):
            s.update(jnp.asarray(steps[i, t]))
        rep = s.metrics_report()
        for k in want:
            want[k].append(rep[k])
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]), err_msg=k)


# ------------------------------------------------- sharded path (P=2) ----
def test_sharded_window_metered_bitwise_subprocess():
    """P=2: the metered sharded window block wraps the UNMODIFIED inner
    executable — outputs bitwise equal to the plain builder's, and the
    riding MetricsState counts the NaN rejection from replicated scalars
    only (no extra collectives, shard-consistent)."""
    script = r"""
import numpy as np, jax, jax.numpy as jnp
jax.config.update("jax_enable_x64", True)
from repro.core import distributed as dkpca, engine as eng, health as hl, \
    inkpca, kernels_fn as kf, telemetry as tm
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
xs = np.asarray(rng.normal(size=(6, 4)))
xs[2] = faults.nan_point(4)
xs = jnp.asarray(xs)
from repro.distributed.sharding import make_mesh
mesh = make_mesh((2,), ("data",))
plan = eng.UpdatePlan(fuse_krow=True, matmul="jnp2",
                      health=hl.DEFAULT_POLICY)
wb = dkpca.make_sharded_window_block(mesh, SPEC, plan=plan)
wbm = dkpca.make_sharded_window_block_metered(mesh, SPEC, plan=plan)
plain = wb(ws.kpca.L, ws.kpca.U, ws.kpca.X, ws.ages, ws.clock, xs,
           ws.kpca.m)
ms = tm.init_metrics(jnp.float64)
metered = wbm(ws.kpca.L, ws.kpca.U, ws.kpca.X, ws.ages, ws.clock, xs,
              ws.kpca.m, ms)
same = all(bool(jnp.array_equal(a, b)) for a, b in zip(plain, metered[:5]))
rep = tm.metrics_report(metered[5])
print("RESULT:" + str({"bitwise": same, "ingests": rep["ingests"],
                       "rejections": rep["rejections"],
                       "evictions": rep["evictions"],
                       "fill": rep["window_fill"]}))
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
    assert res == {"bitwise": True, "ingests": 5, "rejections": 1,
                   "evictions": 5, "fill": 1.0}


# ------------------------------------------------- plan normalization ----
def test_kernel_plan_normalizes_metrics():
    """``metrics`` is dispatch policy, not kernel policy: it must not
    split the jit cache key that kernel_plan() produces."""
    a = eng.UpdatePlan(metrics=True).kernel_plan()
    b = eng.UpdatePlan(metrics=False).kernel_plan()
    assert a == b


# ------------------------------------------------- hub + exporters -------
def test_latency_histogram_compile_split():
    h = obs.LatencyHistogram("update_ms")
    h.add(100.0, key="rung0")    # first per key -> compile bucket
    h.add(1.0, key="rung0")
    h.add(2.0, key="rung0")
    h.add(50.0, key="rung1")
    s = h.summary("update_ms")
    assert s["update_ms_compiles"] == 2
    assert s["update_ms_compile_ms"] == 150.0
    assert s["update_ms_p50"] == 1.5
    assert s["update_ms_max"] == 2.0
    with h.timed(key="rung0") as t:
        t.sync(jnp.ones((2,)))
    assert len(h.ms) == 3


def test_exporter_roundtrip(tmp_path):
    hub = obs.TelemetryHub()
    hub.counter("pub_total").inc(3)
    hub.counter("lm_total", action="admitted").inc(2)
    hub.gauge("drift").set(0.25)
    hist = hub.histogram("query_ms")
    for v in (4.0, 1.0, 2.0, 3.0):
        hist.add(v, key="warm")   # first sample per key -> compile bucket
    hub.emit({"event": "publish", "generation": 1})

    text = hub.to_prometheus()
    parsed = obs.parse_prometheus(text)
    assert parsed["pub_total"] == 3.0
    assert parsed['lm_total{action="admitted"}'] == 2.0
    assert parsed["drift"] == 0.25
    assert parsed['query_ms{quantile="0.5"}'] == 2.0
    assert parsed["query_ms_count"] == 3.0
    assert parsed["query_ms_compiles"] == 1.0
    # every scrape counter/gauge survives the text round trip
    for k, v in hub.scrape().items():
        if k in parsed:
            assert parsed[k] == pytest.approx(v)

    path = tmp_path / "metrics.jsonl"
    obs.write_jsonl(path, hub)
    events = obs.read_jsonl(path)
    assert events[0]["event"] == "publish"
    assert events[-1]["event"] == "scrape"
    assert events[-1]["pub_total"] == 3.0

    srv = obs.serve_metrics(hub, 0)
    try:
        port = srv.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert obs.parse_prometheus(body) == parsed
    finally:
        srv.shutdown()


def test_hub_mirrors_metrics_state():
    hub = obs.TelemetryHub()
    ms = tm.note_publish(tm.init_metrics(), 2)
    hub.observe_metrics_state(ms)
    sc = hub.scrape()
    assert sc["stream_publishes_total"] == 1.0
    assert sc["stream_generation"] == 2.0
    hub.observe_metrics_state(tm.init_metrics_stacked(2), prefix="lane")
    sc = hub.scrape()
    assert sc['lane_m{tenant="1"}'] == 0.0
    assert sc["lane_ingests_total"] == 0.0


def test_kernel_dispatch_counter():
    from repro.kernels.rbf_gram import ops as gops

    hub = obs.fresh_hub()
    x = jnp.ones((4, 2))
    gops.gram(x, x, 1.0)
    gops.gram(x, x, 1.0, force="ref")
    key = 'kernel_dispatch_total{kernel="rbf_gram",route="ref"}'
    assert hub.scrape()[key] == 2.0


# ------------------------------------------------- serving loop ----------
def _make_loop(drift_probe_every, serve_every=1000):
    from repro.launch.serve import IngestServeLoop

    rng = np.random.default_rng(7)
    B, d = 2, 4
    plan = eng.UpdatePlan(serve_every=serve_every, serve_components=4,
                          health=hl.DEFAULT_POLICY)
    batch = eng.StreamBatch(jnp.asarray(rng.normal(size=(B, 4, d))), 16,
                            SPEC, plan=plan, dtype=jnp.float64)
    loop = IngestServeLoop(batch, SPEC, n_components=4,
                           publish_on_drift=10.0,   # never trips
                           drift_probe_every=drift_probe_every,
                           hub=obs.TelemetryHub())
    return loop, rng, (B, d)


@pytest.mark.parametrize("every,expected", [(1, 9), (3, 3)])
def test_drift_probe_rate_limited(every, expected):
    """Regression for the per-ingest drift probe: with ``--publish-on-
    drift`` the probe dispatch must fire every k-th non-publish ingest,
    not every one.  Counted two ways: the loop's own counter and a
    wrapped ``probe_all``."""
    loop, rng, (B, d) = _make_loop(every)
    calls = {"n": 0}
    inner = loop.batch.probe_all

    def counting_probe_all(*a, **k):
        calls["n"] += 1
        return inner(*a, **k)

    loop.batch.probe_all = counting_probe_all
    for _ in range(9):
        loop.ingest(jnp.asarray(rng.normal(size=(B, d))))
    assert loop.drift_probes == expected
    # probe_all also runs inside publish(); none happened here
    assert calls["n"] == expected
    assert loop.generation == 0


def test_drift_trigger_still_fires_with_rate_limit():
    loop, rng, (B, d) = _make_loop(3)
    loop.publish_on_drift = 1e-9    # any motion trips it
    published = 0
    for _ in range(6):
        published += bool(loop.ingest(jnp.asarray(rng.normal(size=(B, d)))))
    assert published >= 1
    assert loop.drift_publishes == published
    assert loop.hub.scrape()["publishes_total"] == published


# ------------------------------------------------- spectral monitor ------
def test_monitor_publishes_hub_gauges_and_drift():
    from repro.spectral import SpectralMonitor

    hub = obs.TelemetryHub()
    rng = np.random.default_rng(8)
    mon = SpectralMonitor(capacity=24, hub=hub)
    s1 = mon.observe(rng.normal(size=(12, 6)))
    assert s1["drift"] == 0.0
    s2 = mon.observe(rng.normal(size=(12, 6)))
    assert s2["drift"] > 0.0
    sc = hub.scrape()
    assert sc["spectral_drift"] == pytest.approx(s2["drift"])
    assert sc["spectral_m"] == s2["m"]
    assert sc["spectral_effective_rank"] == pytest.approx(
        s2["effective_rank"])
