"""Route choice of the eigensystem kernels' ``ops.py`` wrappers.

Each wrapper runs its kernel one of three ways: ``pallas`` (compiled for
the TPU), ``interpret`` (the kernel body run by the Pallas interpreter,
for CPU tests) or ``ref`` (the pure-jnp oracle).  A TPU backend picks
``pallas`` and any other backend ``ref``; ``force=`` or the
``REPRO_PALLAS_FORCE`` environment variable (ref | interpret | pallas)
overrides that.  Every decision is counted in the telemetry hub
(``kernel_dispatch_total{kernel, route}``), so a caller can check after
the fact which route its kernels took.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.obs.hub import note_kernel_dispatch


def route(kernel: str, force: str | None, *operands) -> str:
    """The route ``kernel`` takes for these operands, counted in the hub.

    The compiled TPU kernels are built for 32-bit types: Mosaic has no
    f64 vector unit or accumulator, and with ``jax_enable_x64`` on their
    index arithmetic lowers to 64-bit integers it cannot legalize.  Both
    cases raise here instead of failing inside the TPU compiler."""
    force = force or os.environ.get("REPRO_PALLAS_FORCE") or None
    if force == "ref" or (force is None and jax.default_backend() != "tpu"):
        chosen = "ref"
    elif force == "interpret":
        chosen = "interpret"
    else:
        chosen = "pallas"
    if chosen == "pallas":
        wide = sorted({str(jnp.result_type(a)) for a in operands
                       if jnp.result_type(a).itemsize > 4})
        if wide or jax.config.jax_enable_x64:
            raise ValueError(
                f"{kernel}: the Pallas TPU kernels take 32-bit operands "
                f"with jax_enable_x64 off (got operand dtypes {wide}, "
                f"x64={jax.config.jax_enable_x64}); run the state in "
                f"float32 or use a jnp plan (matmul='jnp'/'jnp2', "
                f"fuse_krow=False)")
    note_kernel_dispatch(kernel, chosen)
    return chosen
