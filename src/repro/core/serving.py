"""Double-buffered snapshot serving: publish-once, query-many.

The ingest engine folds blocks into a *working* state A while queries
(`transform`, KRR predict, Nyström features) batch against a published
immutable ``ServingSnapshot`` B.  A snapshot freezes everything a query
needs — the stored points X, the active count m, and the precomputed
projection matrix

    S = U_active / sqrt(lam)        (transform head; other heads below)

so queries skip the per-call eigpair sort / slice / rescale that
``engine.transform_state`` pays on every invocation: the full argsort of L
and the (M, M) column gather of U happen once per *publication*, not once
per query batch.

One query head serves every workload.  ``query`` computes

    Y, rowsum = K(x_q, X_masked) @ S          (fused kernel or masked gram)
    Y        += affine correction             (mean-adjusted KPCA only)

and the head specializes purely through the published S / affine fields:

* unadjusted KPCA transform:  S = U_act/sqrt(lam),       affine = None
* adjusted KPCA transform:    same S, affine carries the centering
  (colsum = 1ᵀS, colproj = (K1/m)·S, grand = S_sum/m²) — identical to the
  ``transform_state`` post-correction, term for term
* KRR predict:                S = alpha[:, None],        affine = None
* Nyström query features:     S = sqrt(m/n)·U·lam⁺,      affine = None

Publication is O(M·C + M·d) — it never touches the (M, M) eigenvectors
beyond the C-column gather — and the ``retire=`` argument donates a
retired snapshot's buffers to the new one, so the steady-state
double-buffer (``DoubleBuffer``) publishes with no fresh allocation: the
swap itself is a host-side reference flip.  Snapshots are immutable jax
arrays: concurrent ingest into A can never perturb a query against B, and
queries against the same snapshot are bit-identical regardless of what
the ingest engine is doing.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import kernels_fn as kf, rankone
from repro.core.precision import MATMUL_PRECISION

Array = jax.Array


class AffineCorrection(NamedTuple):
    """Mean-adjustment post-correction of a projected query batch (the
    ``transform_state`` centering identity): with rowsum rs per query,

        Y_adj = Y − (rs/mf)·colsumᵀ − 1·colprojᵀ + grand·colsumᵀ
    """

    mf: Array        # ()  active count as float
    colsum: Array    # (C,) 1ᵀS
    colproj: Array   # (C,) (K1/m)·S
    grand: Array     # ()  S_sum/m²


class ServingSnapshot(NamedTuple):
    """Immutable published query state (see module docstring).

    S:          (M, C) precomputed projection matrix (X dtype)
    X:          (M, d) stored points frozen at publication
    m:          ()     active count
    affine:     mean-adjustment correction, or None for linear heads
    generation: ()     int32 publication counter
    """

    S: Array
    X: Array
    m: Array
    affine: AffineCorrection | None
    generation: Array


def _transform_fields(state, *, n_components: int, adjusted: bool):
    """(S, affine) of the KPCA transform head — the per-query prologue of
    ``engine.transform_state`` hoisted to publication time.  Matches it
    bit-for-bit: same masked argsort, same top-C gather, same eps floor."""
    M = state.L.shape[0]
    mask = rankone.active_mask(M, state.m)
    order = jnp.argsort(jnp.where(mask, -state.L, jnp.inf))[:n_components]
    lam = state.L[order]
    vec = state.U[:, order]                        # (M, C) gather — not M²
    denom = jnp.sqrt(jnp.maximum(lam, jnp.finfo(state.L.dtype).eps))
    s_mat = (vec / denom[None, :]).astype(state.X.dtype)
    if not adjusted:
        return s_mat, None
    mf = state.m.astype(state.L.dtype)
    return s_mat, AffineCorrection(mf=mf,
                                   colsum=jnp.sum(s_mat, axis=0),
                                   colproj=jnp.matmul(
                                       state.K1 / mf, s_mat,
                                       precision=MATMUL_PRECISION),
                                   grand=state.S / mf**2)


def _publish_impl(state, generation, *, n_components: int, adjusted: bool):
    s_mat, affine = _transform_fields(state, n_components=n_components,
                                      adjusted=adjusted)
    return ServingSnapshot(S=s_mat, X=state.X, m=state.m, affine=affine,
                           generation=jnp.asarray(generation, jnp.int32))


def _publish_retiring_impl(state, retire, *, n_components: int,
                           adjusted: bool):
    # The retired snapshot is two publications old (double-buffer
    # discipline: the CURRENT front keeps serving while this publish
    # runs), so the new generation is retire.generation + 2.
    return _publish_impl(state, retire.generation + 2,
                         n_components=n_components, adjusted=adjusted)


@lru_cache(maxsize=None)
def _publish_fns(n_components: int, adjusted: bool):
    fresh = jax.jit(partial(_publish_impl, n_components=n_components,
                            adjusted=adjusted))
    donating = jax.jit(partial(_publish_retiring_impl,
                               n_components=n_components,
                               adjusted=adjusted),
                       donate_argnums=(1,))
    return fresh, donating


def publish_transform(state, *, n_components: int, adjusted: bool,
                      generation: int | Array = 0,
                      retire: ServingSnapshot | None = None
                      ) -> ServingSnapshot:
    """Publish a KPCA transform snapshot from (a copy of) the working
    state.  ``retire`` donates a snapshot that is no longer referenced —
    under the ``DoubleBuffer`` alternation, the one retired TWO publishes
    ago — so the new snapshot reuses its buffers instead of allocating;
    its generation is then derived in-graph (retire.generation + 2)."""
    fresh, donating = _publish_fns(int(n_components), bool(adjusted))
    if retire is None:
        return fresh(state, jnp.asarray(generation, jnp.int32))
    return donating(state, retire)


def query(snap: ServingSnapshot, xq: Array, *, spec: kf.KernelSpec,
          plan=None) -> Array:
    """Batch queries against a published snapshot: (nq, d) -> (nq, C).

    Under ``plan.fuse_krow`` the query gram never materializes — the
    fused ``nystrom_recon.transform_project`` kernel contracts each
    kernel tile against S in VMEM; otherwise the masked-gram reference
    path runs.  Pure function of (snap, xq): vmappable across tenants,
    shardable across a tenant mesh axis, and — because snapshots are
    immutable — bit-stable under any concurrent ingest.
    """
    xq = jnp.asarray(xq)
    if plan is not None and getattr(plan, "fuse_krow", False):
        from repro.kernels.nystrom_recon import ops as nops
        y, rs = nops.transform_project(xq, snap.X, snap.S, snap.m,
                                       spec=spec)
    else:
        kq = kf.gram_block(xq.astype(snap.X.dtype), snap.X, spec=spec)
        mask = rankone.active_mask(snap.X.shape[0], snap.m)
        kq = jnp.where(mask[None, :], kq, 0.0)
        y = jnp.matmul(kq, snap.S, precision=MATMUL_PRECISION)
        rs = jnp.sum(kq, axis=1)
    if snap.affine is not None:
        aff = snap.affine
        y = (y - (rs / aff.mf)[:, None] * aff.colsum[None, :]
             - aff.colproj[None, :] + aff.grand * aff.colsum[None, :])
    return y


def query_batch(snaps: ServingSnapshot, xq: Array, *, spec: kf.KernelSpec,
                plan=None) -> Array:
    """Per-tenant queries against tenant-stacked snapshots (leading axis
    B on every leaf, e.g. from ``StreamBatch.publish``):
    (B, nq, d) -> (B, nq, C)."""
    return jax.vmap(lambda s, x: query(s, x, spec=spec, plan=plan))(snaps,
                                                                    xq)


class DoubleBuffer:
    """Host-side double buffer over published snapshots.

    ``front`` is the snapshot queries read; ``publish`` freezes the
    working state into a new front and retires the old one.  The snapshot
    retired two publishes ago is donated to the new publication (its
    buffers become the new snapshot's storage), so steady-state
    publication allocates nothing and the swap is a reference flip —
    O(1) regardless of capacity M.

    **Graceful degradation** (``core/health``): ``publish`` takes a
    ``healthy`` verdict from the caller's probe pass.  An unhealthy
    working state is NEVER frozen into a generation — the buffer keeps
    serving the last healthy front (queries are bit-stable against it by
    immutability) and counts the refusal in ``skipped``, so a drifting
    or NaN-poisoned ingest path degrades to stale-but-correct answers
    instead of serving garbage.  ``ref_lam`` freezes the published
    top-C spectrum alongside each front, giving the staleness-aware
    publication policy its drift reference for free.
    """

    def __init__(self, state=None, *, n_components: int | None = None,
                 adjusted: bool = True):
        self.n_components = n_components
        self.adjusted = adjusted
        self.front: ServingSnapshot | None = None
        self._retired: ServingSnapshot | None = None
        self._generation = 0
        self.skipped = 0
        self.ref_lam: Array | None = None
        if state is not None:
            self.publish(state)

    def publish(self, state, *, n_components: int | None = None,
                adjusted: bool | None = None,
                healthy: bool = True) -> ServingSnapshot:
        nc = self.n_components if n_components is None else n_components
        adj = self.adjusted if adjusted is None else adjusted
        if nc is None:
            raise ValueError("n_components must be set on the buffer or "
                             "passed to publish()")
        if not healthy:
            if self.front is None:
                raise ValueError("refusing to publish an unhealthy state "
                                 "with no prior healthy snapshot to serve")
            self.skipped += 1
            return self.front
        from repro.core import health as hl

        retire, self._retired = self._retired, self.front
        self.front = publish_transform(state, n_components=nc, adjusted=adj,
                                       generation=self._generation,
                                       retire=retire)
        self.ref_lam = hl.top_spectrum(state, nc)
        self._generation += 1
        return self.front

    def query(self, xq: Array, *, spec: kf.KernelSpec, plan=None) -> Array:
        if self.front is None:
            raise ValueError("no snapshot published yet")
        return query(self.front, xq, spec=spec, plan=plan)
