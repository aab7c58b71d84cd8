"""Logical-axis sharding rules (MaxText-style) + constraint helper.

Model code annotates tensors with *logical* dim names; the active rule set
maps them to mesh axes.  Rules are installed by the launcher for the chosen
mesh, so the same model code serves 1-device smoke tests (no rules -> no-op)
and the 512-chip production mesh.

Also exports ``make_mesh``, the one constructor of device meshes in this
repo: every axis is ``AxisType.Auto`` (``jax.make_mesh`` defaults to
``Explicit`` axes, under which ``with_sharding_constraint`` and the
sharded matmuls of the model and KPCA paths are rejected).
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` (sharding
    propagates as in GSPMD; constraints and ``shard_map`` may name any
    axis).  ``devices`` defaults to the first prod(shape) devices."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


DEFAULT_RULES: dict[str, tuple[str, ...] | str | None] = {
    # activations
    "batch": ("pod", "data"),
    "seq": None,
    "seq_shard": "model",        # sequence-parallel regions / decode KV cache
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    "experts": "model",          # TP-EP: experts over 'model', FSDP over 'data'
    "expert_cap": None,
    "landmarks": None,
    # params at rest (FSDP dim + TP dim)
    "fsdp": "data",
    "tp": "model",
    "vocab_fsdp": "data",
    "vocab_tp": "model",         # embedding tables: vocab rows over TP axis
    "recurrent_in": "model",     # sLSTM r_in (overridden to None in §Perf)
    "recurrent_out": "data",
    # EP expert-bank layout: experts over the (pod×)data axis, d_ff over
    # model — fully sharded at rest, consumed in place by the shard_map
    # EP block (moe.py).
    "experts_data": ("pod", "data"),
    "expert_ff": "model",
    "layers": None,
    "conv": None,
    "state": None,
}


def set_rules(rules: dict | None) -> None:
    _state.rules = rules


def get_rules() -> dict | None:
    return getattr(_state, "rules", None)


def set_mesh(mesh: Mesh | None) -> None:
    _state.mesh = mesh


def get_mesh() -> Mesh | None:
    return getattr(_state, "mesh", None)


@contextmanager
def use_mesh(mesh: Mesh, rules: dict | None = None):
    """Install mesh + rules for model tracing; restores previous on exit."""
    prev_mesh, prev_rules = get_mesh(), get_rules()
    set_mesh(mesh)
    rules = dict(DEFAULT_RULES) if rules is None else rules
    # Drop axes the mesh doesn't have (e.g. 'pod' on the single-pod mesh).
    axes = set(mesh.axis_names)

    def _filter(v):
        if v is None:
            return None
        if isinstance(v, str):
            return v if v in axes else None
        vv = tuple(a for a in v if a in axes)
        return vv if vv else None

    set_rules({k: _filter(v) for k, v in rules.items()})
    try:
        yield
    finally:
        set_mesh(prev_mesh)
        set_rules(prev_rules)


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def logical_to_spec(names: tuple[str | None, ...],
                    shape: tuple[int, ...] | None = None) -> P:
    """Map logical dim names to mesh axes; with ``shape`` given, axes that do
    not evenly divide the dim are dropped (e.g. 36 heads on a 16-way axis,
    or a prime vocab) — the tensor falls back to replication on that dim."""
    rules = get_rules() or {}
    mesh = get_mesh()
    out = []
    used: set[str] = set()
    for i, n in enumerate(names):
        axes = rules.get(n) if n else None
        if axes is not None and shape is not None and mesh is not None:
            if shape[i] % _axis_size(mesh, axes) != 0:
                axes = None
        # a mesh axis may shard at most one dim: first dim wins
        if axes is not None:
            alist = (axes,) if isinstance(axes, str) else tuple(axes)
            alist = tuple(a for a in alist if a not in used)
            used.update(alist)
            axes = alist if alist else None
            if axes is not None and shape is not None and mesh is not None:
                if shape[i] % _axis_size(mesh, axes) != 0:
                    axes = None
        out.append(axes)
    return P(*out)


def constrain(x: jax.Array, names: tuple[str | None, ...]) -> jax.Array:
    """with_sharding_constraint by logical names; no-op without a mesh."""
    mesh = get_mesh()
    rules = get_rules()
    if mesh is None or rules is None:
        return x
    spec = logical_to_spec(names, tuple(x.shape))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(names: tuple[str | None, ...],
                   shape: tuple[int, ...] | None = None
                   ) -> NamedSharding | None:
    mesh = get_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, logical_to_spec(names, shape))
