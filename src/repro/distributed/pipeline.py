"""GPipe-style pipeline parallelism over a mesh axis (SPMD formulation).

The stage dimension is a mesh axis (deployment plan: the 'pod' axis, so
inter-stage hops ride the sparse inter-pod links exactly once per
microbatch). All devices run the same program; at schedule step t, stage s
works on microbatch (t - s). Activations move stage→stage+1 with a single
``collective_permute`` per step — the only inter-stage communication.

Bubble fraction is the usual (S-1)/(M+S-1); pick microbatches >> stages.

``pipeline_apply`` is deliberately fn-agnostic: ``stage_fn(params, x)`` is
any per-stage computation (e.g. a slice of transformer periods), and
``stage_params`` carries a leading stage dimension sharded over the stage
axis by the caller (shard_map slices it).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def pipeline_apply(stage_fn: Callable[[Any, Array], Array],
                   stage_params: Any, x: Array, *, mesh: Mesh,
                   axis: str = "pod", microbatches: int | None = None
                   ) -> Array:
    """Run ``x`` through S pipeline stages laid out on mesh axis ``axis``.

    stage_params: pytree with leading dim S on every leaf.
    x: (B, ...) global batch; split into ``microbatches`` (default S).
    Returns stage_{S-1} ∘ ... ∘ stage_0 applied per microbatch.
    """
    S = mesh.shape[axis]
    M = microbatches or S
    B = x.shape[0]
    assert B % M == 0, (B, M)
    mb = B // M

    def body(params, x_local):
        # x_local: (B, ...) replicated over the stage axis inside shard_map;
        # params: this stage's slice (leading dim 1).
        p_stage = jax.tree.map(lambda l: l[0], params)
        sid = jax.lax.axis_index(axis)
        xs = x_local.reshape((M, mb) + x_local.shape[1:])

        n_steps = M + S - 1
        perm = [(i, i + 1) for i in range(S - 1)]

        def step(carry, t):
            buf, outs = carry                      # (mb, ...), (M, mb, ...)
            # stage 0 injects microbatch t (clamped; masked later)
            inj = xs[jnp.minimum(t, M - 1)]
            cur = jnp.where(sid == 0, inj, buf)
            y = stage_fn(p_stage, cur)
            # last stage collects microbatch (t - S + 1)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            valid = (t >= S - 1) & (sid == S - 1)
            upd = jnp.where(valid, y, outs[out_idx])
            outs = jax.lax.dynamic_update_index_in_dim(outs, upd, out_idx,
                                                       axis=0)
            nxt = jax.lax.ppermute(y, axis, perm) if S > 1 else y
            return (nxt, outs), None

        buf0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros((M, mb) + x_local.shape[1:], x_local.dtype)
        (_, outs), _ = jax.lax.scan(step, (buf0, outs0),
                                    jnp.arange(n_steps))
        # results live on the last stage; broadcast to every stage so the
        # out_spec can be replicated over the stage axis.
        outs = jax.lax.psum(
            jnp.where(sid == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs.reshape((B,) + x_local.shape[1:])

    other = [a for a in mesh.axis_names if a != axis]
    in_specs = (jax.tree.map(lambda _: P(axis), stage_params,
                             is_leaf=lambda l: hasattr(l, "shape")),
                P())
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=P(),
                       check_vma=False)
    del other
    return fn(stage_params, x)


def make_stage_fn(block_fn: Callable, n_blocks_per_stage: int):
    """Compose ``n_blocks_per_stage`` applications of block_fn into one
    pipeline stage (params leading dim = blocks within the stage)."""

    def stage_fn(params, x):
        def inner(x, p):
            return block_fn(p, x), None
        y, _ = jax.lax.scan(inner, x, params)
        return y

    return stage_fn
