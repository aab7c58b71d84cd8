"""Pipeline parallelism: single-stage equality + multi-stage equivalence in
a subprocess with forced host devices (the main test process must keep
jax's device count at 1)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.pipeline import pipeline_apply
from repro.distributed.sharding import make_mesh
import pytest


def _stage_fn(p, x):
    return jnp.tanh(x @ p["w"])


def test_single_stage_identity():
    mesh = make_mesh((1,), ("pod",))
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(1, 8, 8)), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
    y = pipeline_apply(_stage_fn, params, x, mesh=mesh, axis="pod",
                       microbatches=2)
    ref = _stage_fn(jax.tree.map(lambda l: l[0], params), x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-6)


@pytest.mark.slow
def test_multi_stage_subprocess():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline_apply
        from repro.distributed.sharding import make_mesh

        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"])

        mesh = make_mesh((4,), ("pod",))
        rng = np.random.default_rng(0)
        params = {"w": jnp.asarray(rng.normal(size=(4, 8, 8)), jnp.float32)}
        x = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
        y = pipeline_apply(stage_fn, params, x, mesh=mesh, axis="pod",
                           microbatches=4)
        ref = x
        for s in range(4):
            ref = stage_fn({"w": params["w"][s]}, ref)
        err = float(jnp.abs(y - ref).max())
        assert err < 1e-5, err
        print("PIPELINE_OK", err)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", script], cwd=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), env=env,
        capture_output=True, text=True, timeout=300)
    assert "PIPELINE_OK" in out.stdout, (out.stdout, out.stderr)
