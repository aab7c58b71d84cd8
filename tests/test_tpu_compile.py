"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode runs the kernel bodies on the CPU but never asks the TPU
compiler whether their tiles are legal or fit the chip's fast memory.
Each test lowers one kernel at the widths ``chip_smoke.py`` runs on the
chip (capacity M=8192, the four-chip row shard (2048, 8192), d=10, a
256-query batch with 8 components), in float32, and compiles it for one
device of a described ``v5e:2x2`` topology.  Nothing runs: a pass says
the compiler accepted the kernel, and the ``tpu_custom_call`` in the
compiled text says the Pallas kernel (not a fallback) is in it.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU compiler's library, and
every test worker imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import kernels_fn as kf
from repro.kernels.eigvec_update.eigvec_update import (eigvec_project,
                                                       eigvec_rotate,
                                                       eigvec_rotate2)
from repro.kernels.nystrom_recon.transform_batch import transform_project
from repro.kernels.rbf_gram.krow_fused import krow_project
from repro.kernels.rbf_gram.rbf_gram import rbf_gram

M = 8192          # capacity of the one-chip stream
SHARD = 2048      # rows of U per device on the four-chip data mesh
D = 10            # input width of the Magic-like stream
Q, C = 256, 8     # query batch and served components
SPEC = kf.KernelSpec(name="rbf", sigma=float(D))


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep the cache out of it.  The
    # chip runs with 64-bit types off (conftest turns them on for the CPU
    # numerics tests), and the kernels are compiled the way it runs them.
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_enable_x64", was[1])


def _f32(sh, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sh)


def _i32(sh, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sh)


def _rotate(sh, rows):
    vec = _f32(sh, M)
    return eigvec_rotate, (_f32(sh, rows, M), vec, vec, vec, vec,
                           _i32(sh), _i32(sh))


def _rotate2(sh, rows):
    vec, cid = _f32(sh, M), _i32(sh, M)
    factor = (vec, vec, vec, vec, vec, cid)
    return eigvec_rotate2, (_f32(sh, rows, M), *factor, *factor,
                            _i32(sh), _i32(sh))


def _project(sh, rows):
    return eigvec_project, (_f32(sh, rows, M), _f32(sh, rows, 2),
                            _i32(sh), _i32(sh))


def _krow(sh, rows):
    fn = functools.partial(krow_project, spec=SPEC)
    return fn, (_f32(sh, rows, M), _f32(sh, rows, D), _f32(sh, D),
                _f32(sh, rows, 2), _i32(sh), _i32(sh))


def _transform(sh, rows):
    fn = functools.partial(transform_project, spec=SPEC)
    return fn, (_f32(sh, Q, D), _f32(sh, rows, D), _f32(sh, rows, C),
                _i32(sh))


def _gram(sh, rows):
    return rbf_gram, (_f32(sh, rows, D), _f32(sh, M, D), _f32(sh))


CASES = {
    "eigvec_rotate": (_rotate, M),
    "eigvec_rotate_shard": (_rotate, SHARD),
    "eigvec_rotate2": (_rotate2, M),
    "eigvec_rotate2_shard": (_rotate2, SHARD),
    "eigvec_project": (_project, M),
    "krow_project": (_krow, M),
    "transform_project": (_transform, M),
    "rbf_gram": (_gram, M),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    build, rows = CASES[case]
    fn, args = build(one_chip, rows)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
