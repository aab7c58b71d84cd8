"""Elastic scaling: a checkpoint written on an N-device mesh restores onto
an M-device mesh (subprocess with forced host devices — the main process
keeps 1 device)."""
import os
import subprocess
import sys
import textwrap
import pytest

pytestmark = pytest.mark.slow  # model-zoo / driver integration tier


def test_reshard_4_to_2_devices(tmp_path):
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import save_checkpoint, load_checkpoint
        from repro.distributed.sharding import make_mesh

        d = {str(tmp_path)!r}
        mesh4 = make_mesh((4,), ("data",))
        sh4 = NamedSharding(mesh4, P("data"))
        tree = {{"w": jax.device_put(jnp.arange(16, dtype=jnp.float32), sh4),
                 "b": jax.device_put(jnp.ones((4, 8), jnp.bfloat16),
                                     NamedSharding(mesh4, P("data", None)))}}
        save_checkpoint(d, 1, tree)

        # restore onto a 2-device mesh (simulating shrink-after-failure)
        mesh2 = make_mesh((2,), ("data",), devices=jax.devices()[:2])
        sh2 = NamedSharding(mesh2, P("data"))
        target = {{"w": jax.ShapeDtypeStruct((16,), jnp.float32,
                                             sharding=sh2),
                   "b": jax.ShapeDtypeStruct((4, 8), jnp.bfloat16,
                                             sharding=NamedSharding(
                                                 mesh2, P("data", None)))}}
        out = load_checkpoint(d, 1, target)
        assert out["w"].sharding == sh2
        np.testing.assert_array_equal(np.asarray(out["w"]), np.arange(16))
        np.testing.assert_array_equal(
            np.asarray(out["b"], np.float32), np.ones((4, 8)))
        print("ELASTIC_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", script], cwd=root, env=env,
                         capture_output=True, text=True, timeout=300)
    assert "ELASTIC_OK" in out.stdout, (out.stdout, out.stderr)
