"""Matmul precision of every float32 contraction on the eigensystem path.

The maintained eigenvectors are rotated by a few matmuls per streamed
point, and each rotation's rounding error stays in U for the rest of the
stream.  On a TPU, XLA's DEFAULT precision runs an f32 dot as a single
bfloat16 pass (relative error ~4e-4 of |A||B| on a v5e), which pushes
the orthogonality residual ‖UᵀU − I‖ past the health policy's
``orth_tol`` within a few dozen windowed steps at M=8192 and fires the
heal ladder on a clean stream.  HIGHEST keeps each product at f32
rounding (~1e-7), so drift grows as it does on a CPU.  HIGHEST is also
the only non-default precision the Pallas TPU compiler accepts for a
``jnp.dot`` inside a kernel, so the jnp and the Pallas routes round the
same way.  CPU backends compute f32 dots exactly in f32 at every
precision, so this constant changes nothing off the TPU.

Every contraction in ``core/`` and ``kernels/`` that touches the
eigensystem, its kernel rows or its queries passes ``precision=`` this
constant; nothing sets ``jax_default_matmul_precision`` globally, so
other code in the process keeps its own choice.
"""
from __future__ import annotations

import jax

MATMUL_PRECISION = jax.lax.Precision.HIGHEST
