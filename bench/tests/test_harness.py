"""CPU checks of the harness: lookup by name, the open-loop schedule,
latency from the due time, percentiles over all requests, and the
refusal to run without a TPU."""
import json
import math
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from conftest import BENCH, ROOT

import run as bench_run
from harness import cell as hc, schedule, stats


SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_cell_files_found_by_name(cell):
    c = bench_run.load_cell(cell)
    assert c["config"]["name"] == c["cell"]["config"]
    assert c["traffic"] == json.loads(
        (BENCH / "traffic" / f"{c['cell']['traffic']}.json").read_text())
    names = {m["name"] for m in c["e2e"]}
    assert "setup_s" in names and len(names) >= 2
    assert c["per_layer"]
    for m in c["per_layer"]:
        assert callable(bench_run.load_reader(m["name"]))


def test_every_config_file_and_reference_exists():
    for cfg in SPEC["configs"]:
        data = json.loads((ROOT / cfg["file"]).read_text())
        assert data["name"] == cfg["name"]
        assert set(cfg["reduced"]) <= set(data)
        assert (BENCH / "configs" / f"{data['reference']}.py").exists()


def test_every_metric_has_a_reader():
    for m in SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists(), m["name"]


@pytest.mark.parametrize("process", [
    {"process": "poisson", "per_s": 20.0},
    {"process": "fixed", "per_s": 3.0}])
def test_schedule_same_seed_same_arrivals(process):
    a = schedule.offsets(process, 30.0, np.random.default_rng([7, 2]))
    b = schedule.offsets(process, 30.0, np.random.default_rng([7, 2]))
    np.testing.assert_array_equal(a, b)
    assert a.size == round(process["per_s"] * 30.0)
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 30.0


def test_schedule_seeds_differ_in_order_not_in_amount():
    spec = {"process": "poisson", "per_s": 20.0}
    a = schedule.offsets(spec, 30.0, np.random.default_rng([1, 2]))
    b = schedule.offsets(spec, 30.0, np.random.default_rng([2, 2]))
    assert a.size == b.size
    assert not np.array_equal(a, b)
    # the same set of gaps, in another order
    np.testing.assert_allclose(np.sort(np.diff(np.r_[a, 30.0])),
                               np.sort(np.diff(np.r_[b, 30.0])), atol=1e-9)


def test_order_seed_gives_every_run_seed_the_same_arrivals():
    spec = {"process": "poisson", "per_s": 20.0, "order_seed": 0}
    a = schedule.offsets(spec, 30.0, np.random.default_rng([1, 2]))
    b = schedule.offsets(spec, 30.0, np.random.default_rng([2, 2]))
    np.testing.assert_array_equal(a, b)
    free = schedule.offsets({"process": "poisson", "per_s": 20.0}, 30.0,
                            np.random.default_rng([1, 2]))
    np.testing.assert_allclose(np.sort(np.diff(np.r_[a, 30.0])),
                               np.sort(np.diff(np.r_[free, 30.0])), atol=1e-9)


def test_percentile_counts_every_request():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    # one missing request among 100 lies above the 95th percentile ...
    assert math.isfinite(stats.percentile(xs[:99] + [math.inf], 95))
    # ... ten of them reach it
    assert stats.percentile(xs[:90] + [math.inf] * 10, 95) == math.inf
    assert math.isnan(stats.percentile([], 95))


class FakeService:
    """Stands in for ``cell.Service``: fixed service times, one of them
    stalled, so the window loop's timing can be checked on its own."""

    def __init__(self, q_s=0.002, step_s=0.01, stall_at=None, stall_s=0.0):
        self.B = 2
        self.steps = self.published = 0
        self.q_s, self.step_s = q_s, step_s
        self.stall_at, self.stall_s = stall_at, stall_s

    def make_query(self):
        return None

    def query(self, q, trace):
        time.sleep(self.q_s)
        return np.zeros((self.B, 1, 1)), self.published

    def ingest(self, trace):
        time.sleep(self.step_s + (self.stall_s if self.steps == self.stall_at
                                  else 0.0))
        self.steps += 1
        self.published = self.steps
        return True


TRAFFIC = {"ingest": {"mode": "open", "process": "fixed", "per_s": 10.0},
           "queries": {"process": "poisson", "per_s": 50.0, "rows": 1},
           "drain_s": 5.0}


def test_latency_is_timed_from_the_due_time():
    svc = FakeService()
    w = hc.run_window(svc, TRAFFIC, 1.0, seed=5, sample_n=0)
    assert len(w.q_lat) == 50 and len(w.s_due) == 10
    assert min(w.q_lat) >= svc.q_s
    stale = [p - d for d, p in zip(w.s_due, w.s_pub)]
    assert min(stale) >= svc.step_s
    attempted, failed = hc.counts(w, "open")
    assert (attempted, failed) == (50 + 10 * svc.B, 0)


def test_a_stalled_step_raises_the_tail():
    base = hc.run_window(FakeService(), TRAFFIC, 1.0, seed=5, sample_n=0)
    slow = hc.run_window(FakeService(stall_at=2, stall_s=0.4), TRAFFIC, 1.0,
                         seed=5, sample_n=0)
    e0, e1 = hc.end_to_end(base, "open"), hc.end_to_end(slow, "open")
    # Queries due during the stall wait for it: their latency counts the
    # wait, though each query alone is as fast as before.
    assert e1["query_p95_ms"] > e0["query_p95_ms"] + 100
    assert e1["staleness_p95_ms"] > e0["staleness_p95_ms"] + 100


def test_staleness_reader_is_the_windows_tail():
    w = hc.run_window(FakeService(stall_at=2, stall_s=0.4), TRAFFIC, 1.0,
                      seed=5, sample_n=0)
    value = bench_run.load_reader("staleness_p95_ms")(
        bench_run.Run(None, w))
    assert value == hc.end_to_end(w, "open")["staleness_p95_ms"]
    assert value > 400


def test_closed_ingest_rate_is_all_work_over_all_time():
    svc = FakeService(step_s=0.05)
    tr = {"ingest": {"mode": "closed"}, "queries": None, "drain_s": 5.0}
    w = hc.run_window(svc, tr, 1.0, seed=1, sample_n=0)
    rate = hc.end_to_end(w, "closed")["ingest_pts_per_s"]
    steps = len(w.s_due)
    assert steps >= 15
    assert rate == pytest.approx(steps * svc.B / (max(w.s_pub) - w.t0))


def test_requests_left_after_the_drain_fail():
    svc = FakeService(step_s=0.3)
    tr = dict(TRAFFIC, drain_s=0.2)
    w = hc.run_window(svc, tr, 1.0, seed=5, sample_n=0)
    attempted, failed = hc.counts(w, "open")
    assert failed > 0
    assert hc.end_to_end(w, "open")["staleness_p95_ms"] == math.inf


def _run(cmd, cwd, env):
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _run([sys.executable, "bench/run.py", "--workload",
              "magic-8x2048.read", "--seed", "1", "--seconds", "1",
              "--trace", "0"], ROOT, env)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = _run([sys.executable, "bench/run.py", "--workload",
              "magic-8x2048.read", "--seed", "1", "--seconds", "1",
              "--trace", "0"], tmp_path, env)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
