"""Fault-injection harness: controlled corruption + crash points.

Two halves:

**Killpoints** — production code paths embed named ``faults.trip(point)``
calls at the instants a real deployment can die (between the checkpoint
writes and renames, for instance).  ``trip`` is a no-op unless a test
``arm``-ed that point, in which case it raises ``FaultInjected`` —
simulating a kill -9 at exactly that line.  The registry is process-local
and intentionally trivial: ``trip`` costs one dict check when nothing is
armed, so shipping the killpoints in production code is free.

**Corruptors** — pure functions that damage eigensystem state in
controlled, realistic ways (a NaN input point, a bit-flipped eigenvector
tile, a poisoned stored row) so the detection + recovery path
(``core/health``) can be asserted end-to-end.

Used by ``tests/test_faults.py`` / ``tests/test_health.py`` and the
``make faults`` target.
"""
from __future__ import annotations

from contextlib import contextmanager

import numpy as np

__all__ = ["FaultInjected", "arm", "disarm", "armed", "trip", "injected",
           "nan_point", "corrupt_eigvecs", "bitflip_eigvec",
           "corrupt_eigenvalue", "poison_stored_row"]


class FaultInjected(BaseException):
    """Raised at an armed killpoint.  Derives from BaseException so
    production ``except Exception`` recovery blocks do NOT swallow it —
    a killed process doesn't run its own exception handlers either."""

    def __init__(self, point: str):
        super().__init__(f"injected fault at {point!r}")
        self.point = point


_armed: dict[str, int] = {}
_hits: dict[str, int] = {}


def arm(point: str, *, after: int = 0) -> None:
    """Arm ``point``: the (after+1)-th ``trip(point)`` raises."""
    _armed[point] = int(after)
    _hits[point] = 0


def disarm(point: str | None = None) -> None:
    """Disarm one point, or everything when called with no argument."""
    if point is None:
        _armed.clear()
        _hits.clear()
    else:
        _armed.pop(point, None)
        _hits.pop(point, None)


def armed(point: str) -> bool:
    return point in _armed


def trip(point: str) -> None:
    """Killpoint: no-op unless armed (one dict lookup on the fast path)."""
    if not _armed or point not in _armed:
        return
    _hits[point] = _hits.get(point, 0) + 1
    if _hits[point] > _armed[point]:
        disarm(point)
        raise FaultInjected(point)


@contextmanager
def injected(point: str, *, after: int = 0):
    """Scope an armed killpoint; always disarms on exit."""
    arm(point, after=after)
    try:
        yield
    finally:
        disarm(point)


# ------------------------------------------------------------ corruptors --
def nan_point(d: int, *, kind: str = "nan", index: int = 0,
              base=None) -> np.ndarray:
    """A d-dimensional input point with a non-finite entry — the
    canonical bad arrival the quarantine gate must reject."""
    x = (np.zeros(d, np.float32) if base is None
         else np.array(base, np.float32, copy=True))
    x[index] = {"nan": np.nan, "inf": np.inf, "-inf": -np.inf}[kind]
    return x


def corrupt_eigvecs(state, *, magnitude: float = 0.1, seed: int = 0):
    """Additive gaussian damage to the ACTIVE eigenvector block — models
    slow orthogonality drift (or a partial HBM scribble) that the
    probe must detect and ``heal`` must repair.  Keeps the
    padding invariants (only rows/cols < m are touched)."""
    import jax.numpy as jnp

    m = int(state.m)
    rng = np.random.default_rng(seed)
    noise = rng.normal(scale=magnitude, size=(m, m))
    U = state.U.at[:m, :m].add(jnp.asarray(noise, state.U.dtype))
    return state._replace(U=U)


def bitflip_eigvec(state, i: int = 0, j: int = 0, *, bit: int = 31):
    """Flip one bit of eigenvector entry U[i, j] — a literal SDC
    (silent-data-corruption) event.  Bit 31 of an f32 is the sign bit;
    bit 30 scribbles the exponent (a huge entry the non-finite /
    negativity probes catch even when orthogonality sampling misses
    column j)."""
    import jax.numpy as jnp

    U = np.asarray(state.U).copy()
    if U.dtype == np.float32:
        U.view(np.uint32)[i, j] ^= np.uint32(1) << np.uint32(bit)
    elif U.dtype == np.float64:
        U.view(np.uint64)[i, j] ^= np.uint64(1) << np.uint64(bit)
    else:
        raise TypeError(f"bitflip_eigvec supports f32/f64, got {U.dtype}")
    return state._replace(U=jnp.asarray(U))


def corrupt_eigenvalue(state, j: int = 0, *, value: float = -1.0):
    """Overwrite an active eigenvalue — PSD violation the negativity
    probe flags."""
    import jax.numpy as jnp

    return state._replace(L=state.L.at[j].set(jnp.asarray(value,
                                                          state.L.dtype)))


def poison_stored_row(state, row: int = 0):
    """NaN a stored point row — makes in-place resync impossible, forcing
    the restore-from-checkpoint rung (``health.HealthError``)."""
    import jax.numpy as jnp

    return state._replace(X=state.X.at[row].set(jnp.nan))
