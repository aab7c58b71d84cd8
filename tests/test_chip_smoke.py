"""``chip_smoke.py``'s phases at tiny sizes on the CPU, and its refusal to
report a result without a TPU.

The phases take their sizes as arguments, so the same code that checks
the chip at M=8192 runs here at M≤64 against the same float64 references
and tolerance model.  On the CPU the kernel plan's kernels take the
route ``REPRO_PALLAS_FORCE`` picks (conftest: the jnp oracles), which is
what ``expect_route`` says.
"""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def stream():
    from repro.data.uci_like import load_dataset

    return load_dataset("magic", n=700, seed=3)


ROUTE = os.environ.get("REPRO_PALLAS_FORCE", "ref")


@pytest.mark.parametrize("plan_name", ["default", "kernel"])
def test_window_phase_tiny(smoke, stream, plan_name):
    out = smoke.phase_window(stream, plan_name=plan_name, capacity=64,
                             window=48, steps=8, every=4,
                             expect_route=ROUTE)
    assert out["heals"] == 0
    assert out["eig_rel_err"] <= out["tol"]["eig"]


def test_tenants_phase_tiny(smoke, stream):
    out = smoke.phase_tenants(stream, tenants=2, capacity=32, window=32,
                              steps=3, query_rate=2, expect_route=ROUTE)
    assert out["skipped_publishes"] == 0 and out["generations"] == 3


def test_nystrom_phase_tiny(smoke, stream):
    out = smoke.phase_nystrom(stream[:120], capacity=16)
    assert out["trace_error_err"] <= out["tol"]


def test_window_phase_fails_on_a_broken_tolerance(smoke, stream,
                                                  monkeypatch):
    """A check that cannot pass makes the phase raise, not carry on."""
    monkeypatch.setattr(smoke, "SAFETY", 0.0)
    with pytest.raises(smoke.SmokeFailure, match="eigenvalue error"):
        smoke.phase_window(stream, plan_name="default", capacity=64,
                           window=48, steps=8, every=4, expect_route=ROUTE)


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            assert not json.loads(line).get("ok")


def test_refuses_alone_in_a_directory(tmp_path):
    """Copied out of the checkout, the script cannot import the program
    and must not pretend otherwise."""
    lone = tmp_path / "chip_smoke.py"
    lone.write_text((ROOT / "chip_smoke.py").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, str(lone)], env=env,
                          cwd=tmp_path, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
