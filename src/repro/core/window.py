"""Sliding-window incremental KPCA: bounded memory, unbounded streams.

``KPCAStream(window=W)`` tracks the exact mean-adjusted (or raw) kernel
eigensystem of the **trailing W points** of an endless stream: once the
window is full, every ingested point first evicts the oldest one via the
decremental pipeline (``core/downdate.py``) and then folds in as usual —
so per-step cost stays at the window's bucket forever and memory never
grows, which is what the ROADMAP's unbounded-stream serving scenario
requires (append-only streams saturate at capacity instead).

The FIFO ordering is carried **in the state** as an arrival-index ring
(``ages``/``clock``), not as host-side stream bookkeeping, so a windowed
stream checkpointed mid-window restores and continues identically to an
uninterrupted run.  Carrying it in-state is also what lets the
steady-state scan (``engine.Engine.window_block``) advance the ring
inside ``lax.scan`` — victim selection (argmin of ages) needs no host
round-trip, so a full-window block folds in ONE dispatch.  The eviction permutation (``downdate.boundary_perm``)
preserves the survivors' arrival order, so physically the oldest active
point is always row argmin(ages) — row 0 for a pure FIFO stream — but
the ring stays authoritative across replace-arbitrary-row calls and
checkpoint round-trips.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import downdate as dd
from repro.core import engine as eng
from repro.core import kernels_fn as kf

Array = jax.Array

def age_sentinel(dtype) -> int:
    """Inactive-slot marker: far above any real arrival index.  Derived
    from the REALIZED dtype — without x64, int64 requests silently become
    int32 and a fixed 2⁶² constant would overflow into a negative value
    that argmin then prefers over live rows."""
    return int(jnp.iinfo(dtype).max // 2)


class WindowState(NamedTuple):
    """A ``KPCAState`` plus the FIFO arrival ring.

    kpca:  the fixed-capacity eigensystem state (see ``inkpca.KPCAState``)
    ages:  (M,) arrival index of the point in each physical row, in the
           realized integer dtype (int64 under x64, int32 otherwise —
           which is why ``rebase_ages`` exists); ``age_sentinel(dtype)``
           marks inactive rows
    clock: ()  arrival index of the next ingested point (same dtype)
    """

    kpca: object
    ages: Array
    clock: Array


def init_window(x0: Array, capacity: int, spec: kf.KernelSpec, *,
                adjusted: bool = True, dtype=jnp.float32) -> WindowState:
    from repro.core import inkpca

    kpca = inkpca.init_state(x0, capacity, spec, adjusted=adjusted,
                             dtype=dtype)
    m0 = x0.shape[0]
    dtype = jax.dtypes.canonicalize_dtype(jnp.int64)   # int32 without x64
    ages = jnp.full((capacity,), age_sentinel(dtype), dtype)
    ages = ages.at[:m0].set(jnp.arange(m0, dtype=dtype))
    return WindowState(kpca=kpca, ages=ages,
                       clock=jnp.asarray(m0, ages.dtype))


def oldest_row(wstate: WindowState) -> int:
    """Physical row of the oldest active point (host-side read)."""
    return int(jnp.argmin(wstate.ages))


def evict(engine: eng.Engine, wstate: WindowState, row: int, *,
          min_rows: int = 0) -> WindowState:
    """Remove the point in physical ``row`` and update the ages ring with
    the same survivor-order-preserving permutation the downdate applied."""
    kpca = engine.downdate(wstate.kpca, row, min_rows=min_rows)
    order = dd.boundary_perm(jnp.asarray(row, jnp.int32), wstate.kpca.m,
                             wstate.ages.shape[0])
    ages = wstate.ages[order].at[wstate.kpca.m - 1].set(
        age_sentinel(wstate.ages.dtype))
    return wstate._replace(kpca=kpca, ages=ages)


def rebase_ages(wstate: WindowState) -> WindowState:
    """Shift all active arrival stamps (and the clock) down so the clock
    restarts at ``capacity``.  Active ages live in [clock − m, clock), so
    subtracting clock − capacity preserves their order and keeps them
    non-negative; sentinel slots stay sentinels.  Called when the clock
    nears the sentinel — without x64 the ring is int32 and a forever
    stream would otherwise collide with the sentinel after ~10⁹ points
    (argmin would then pick an inactive slot and eviction would raise).
    """
    sent = age_sentinel(wstate.ages.dtype)
    base = wstate.clock - wstate.ages.shape[0]
    ages = jnp.where(wstate.ages == sent, sent, wstate.ages - base)
    return wstate._replace(ages=ages, clock=wstate.clock - base)


def maybe_rebase(wstate: WindowState) -> WindowState:
    """Traced rebase guard: rebase when the clock nears the sentinel,
    selected with ``jnp.where`` so the check never forces a device sync
    (the rebase arithmetic is O(M) elementwise — cheaper than the sync
    the old host-side ``int(clock)`` comparison paid on every step)."""
    sent = age_sentinel(wstate.ages.dtype)
    reb = rebase_ages(wstate)
    need = wstate.clock >= sent - 1
    return wstate._replace(ages=jnp.where(need, reb.ages, wstate.ages),
                           clock=jnp.where(need, reb.clock, wstate.clock))


def stamp_grown_ages(wstate: WindowState, grown, count: int) -> WindowState:
    """Stamp arrival indices for ``count`` append-only points just folded
    into ``grown`` (a KPCAState) — the growth-phase half of
    ``Engine.window_block``.  ``count`` and the pre-growth active count
    are host values, so the stamp is one fused slice write."""
    m0 = int(wstate.kpca.m)
    stamps = wstate.clock + jnp.arange(count, dtype=wstate.ages.dtype)
    ages = jax.lax.dynamic_update_slice(wstate.ages, stamps, (m0,))
    return WindowState(kpca=grown, ages=ages, clock=wstate.clock + count)


def ingest(engine: eng.Engine, wstate: WindowState, x_new: Array, *,
           window: int, min_rows: int = 0, hstate=None):
    """One sliding-window step: evict-oldest if the window is full, then
    fold the new point in and stamp its arrival index.

    The evict decision reads ``int(m)`` on the host (the same sync bucket
    selection already pays); the rebase guard is traced.  For steady-state
    blocks use ``Engine.window_block`` — one scanned dispatch, no host
    syncs inside the block.

    With a health policy on the plan (``plan.health``) the point goes
    through the quarantine gate first — a rejected (non-finite/outlier)
    point leaves the eigensystem, the arrival ring, the ages AND the
    clock untouched, so evict order stays consistent with a stream that
    never saw it.  (The old behaviour evicted and stamped regardless,
    which skewed the ring even though the update should not happen.)
    Pass ``hstate`` (a ``health.HealthState``) to also receive the
    updated probe/quarantine counters: returns ``(wstate, hstate)``;
    without it, returns ``wstate`` alone.

    This is now a thin spelling of the composed pipeline: the bundle's
    ``ages`` member selects the evict stage, ``plan.health`` decides the
    gate stage (see ``engine.Engine.step``).
    """
    policy = getattr(engine.plan, "health", None)
    h = None
    if policy is not None:
        from repro.core import health as hl

        h = hstate if hstate is not None else hl.init_health(
            wstate.kpca.L.dtype)
    s = engine.step(eng.make_stream(wstate, health=h), x_new,
                    window=window, min_rows=min_rows)
    out = WindowState(kpca=s.kpca, ages=s.ages, clock=s.clock)
    if policy is not None and hstate is not None:
        return out, s.health
    return out
