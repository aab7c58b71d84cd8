#!/usr/bin/env python3
"""Bring-up check of the windowed KPCA / Nyström service on one TPU chip.

Drives the service's main path once, at a real state size, through the
objects a user of ``launch/serve.py`` drives, and checks every result
against an independent float64 reference computed on the host:

  A  one sliding-window stream on the Magic-like data (d=10, RBF with
     sigma = d, mean-adjusted, float32): capacity = window = 8192 seeded
     from the first 8192 points, then 64 more points through
     ``KPCAStream.update_block`` (``Engine.step_block``; every point
     evicts the oldest, so the downdate runs) with a 256-query transform
     every 16 points.  Run under the default plan and under the kernel
     plan (``matmul="pallas2"``, ``fuse_krow=True``), whose kernels must
     all take the compiled ``pallas`` route.
  B  the decoupled multi-tenant service (``StreamBatch`` +
     ``IngestServeLoop``, as ``serve.py --mode kpca --decouple --tenants 8
     --health``): 8 tenants, capacity = window = 2048, kernel plan, a
     publish every block, 4 query micro-batches of 256 per step.
  C  the Nyström leverage lifecycle behind ``serve.py --mode nystrom
     --landmark-policy leverage`` over the whole Magic-like stream.

``--four-chips`` runs only the row-sharded window step on a 4-device
``data`` mesh at M=8192 and the single-device engine on the same stream.

Each phase prints one line with its numbers, compile seconds (JAX's own
trace + lower + compile durations) kept apart from steady seconds.  The
last line is ``{"ok": true, "device": {...}}``; any failed check prints
its reason and the script exits 1 without that line.  Without a TPU it
exits 2.  The compile cache follows ``JAX_COMPILATION_CACHE_DIR`` or
lives in ``<checkout>/.jax_cache``.

    python chip_smoke.py [--seed N] [--four-chips]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import warnings
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

EPS32 = float(np.finfo(np.float32).eps)
N_COMP = 8            # components served and checked
QUERY_BATCH = 256


class SmokeFailure(Exception):
    """A check of a phase failed; the message says which and by how much."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (its own
    monitoring events), so a phase can report compile time apart from
    the steady time of the work it then runs."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self._EVENTS:
            self.seconds += duration


def _compile_s(clock, c0):
    return None if clock is None else round(clock.seconds - c0, 3)


def _clock0(clock):
    return 0.0 if clock is None else clock.seconds


# --------------------------------------------------------------- reference --
def rbf(x, y, sigma):
    """k(x, y) = exp(-|x - y|² / sigma) in float64 (``kernels_fn`` RBF)."""
    d2 = (np.sum(x * x, 1)[:, None] + np.sum(y * y, 1)[None, :]
          - 2.0 * (x @ y.T))
    return np.exp(-np.maximum(d2, 0.0) / sigma)


def window_reference(xw, xq, sigma, k, *, centred=True):
    """Top-(k+1) eigenpairs of the centred gram of the window ``xw`` and
    the projected query gram of ``xq`` on the top-k subspace, float64
    (``centred=False``: the raw gram, no queries).

    The centred gram is K' = (I - 1/n)K(I - 1/n); a query's centred
    kernel row is k_q - mean(k_q) - mean_rows(K) + mean(K), the
    centering ``transform`` applies.  The query quantity compared is
    G = (K'_q U)(K'_q U)ᵀ = Y Λ Yᵀ with Y = K'_q U Λ^-1/2 the served
    projection: it does not depend on the sign or on a rotation of
    eigenvectors within the top-k subspace."""
    from scipy.sparse.linalg import eigsh

    K = rbf(xw, xw, sigma)
    r = K.mean(axis=1)
    mu = r.mean()
    Kc = K - r[:, None] - r[None, :] + mu if centred else K
    lam, vec = eigsh(Kc, k=k + 1, which="LA")
    order = np.argsort(lam)[::-1]
    lam, vec = lam[order], vec[:, order]
    out = {"lam": lam, "U": vec[:, :k], "lam1": float(lam[0]),
           "gap": float(lam[k - 1] - lam[k])}
    if not centred:
        return out
    Kq = rbf(xq, xw, sigma)
    Kqc = Kq - Kq.mean(axis=1, keepdims=True) - r[None, :] + mu
    P = Kqc @ vec[:, :k]
    inside = float(np.sum(P * P))
    outside = max(float(np.sum(Kqc * Kqc)) - inside, 0.0)
    return {**out, "G": P @ P.T, "q_out": math.sqrt(outside / inside),
            "Kqc": Kqc}


def compare_window(state, y, ref, k):
    """Errors of a maintained state and its served projections ``y``
    (Q, k; None to skip them) against ``window_reference``: max relative
    eigenvalue error, largest principal angle of the top-k subspace,
    relative errors of the projected query gram and of the served rows."""
    import jax.numpy as jnp

    from repro.core import engine as eng

    lam, vec = eng.eigpairs(state)
    lam = np.asarray(lam[:k], np.float64)
    m = int(state.m)
    Uc = np.asarray(vec[:m, :k], np.float64)
    eig = float(np.max(np.abs(lam - ref["lam"][:k]) / ref["lam"][:k]))
    Q, _ = np.linalg.qr(Uc)
    R = Q - ref["U"] @ (ref["U"].T @ Q)
    sin_max = float(np.linalg.norm(R, 2))
    angle = math.asin(min(sin_max, 1.0))
    if y is None:
        return {"eig_rel_err": eig, "max_angle": angle}
    yc = np.asarray(jnp.asarray(y), np.float64)
    G = (yc * lam[None, :]) @ yc.T
    query = float(np.linalg.norm(G - ref["G"]) / np.linalg.norm(ref["G"]))
    # The served rows against the same state's projection in float64:
    # the serving path (kernel rows, centering, projection) on its own.
    y_host = ref["Kqc"] @ (Uc / np.sqrt(lam)[None, :])
    serve = float(np.linalg.norm(yc - y_host) / np.linalg.norm(y_host))
    return {"eig_rel_err": eig, "max_angle": angle, "query_err": query,
            "serve_err": serve}


# -------------------------------------------------------------- tolerances --
# Error model of a maintained f32 eigensystem (the basis of every bound
# below).  A rank-one update is backward stable: it returns the exact
# eigensystem of a matrix within c·eps·‖A‖₂ of the updated one, ‖A‖₂ = λ₁.
# The rounding errors of the N updates since the batch initialisation
# (and of the batch eigh) are independent, so they add as a random walk,
# and the maintained U Λ Uᵀ differs from the exact gram by a symmetric E
# with
#
#     ‖E‖₂ ≈ SAFETY · eps · λ₁ · sqrt(N + 1).
#
# Weyl's theorem moves every eigenvalue by at most ‖E‖₂; the Davis–Kahan
# sin θ theorem turns the top-k subspace by at most ‖E‖₂ / gap, gap =
# λ_k − λ_{k+1}.  SAFETY absorbs the constant c (secular solve, the
# Gu–Eisenstat z-recompute, the f32 kernel rows and the chip's own f32
# arithmetic).  It is empirical, not derived: CPU runs reach c ≈ 3
# (W=1024 to 8192, up to 64 points), and SAFETY allows ten times that.
# A chip run that needs more is a defect to find, not a bound to raise
# (a TPU-only error in the Gu–Eisenstat power of two once needed 256).
SAFETY = 32.0


def perturbation(lam1, n_updates):
    """‖E‖₂ of the model above."""
    return SAFETY * EPS32 * lam1 * math.sqrt(n_updates + 1)


def window_tolerances(ref, n_updates, k, angle=None):
    """Bounds of the window model.  ``angle`` is the measured top-k
    principal angle, which the query bound is conditioned on (None
    without queries)."""
    E = perturbation(ref["lam1"], n_updates)
    # Weyl, relative to the smallest checked eigenvalue λ_k.
    eig = E / ref["lam"][k - 1]
    # Davis–Kahan sin θ theorem for the top-k subspace.
    angle_tol = math.asin(min(1.0, E / ref["gap"]))
    if angle is None:
        return {"eig": eig, "angle": angle_tol}
    # G = K'_q P K'_qᵀ with P the top-k projector (the served 1/sqrt(λ)
    # scaling cancels against Λ exactly).  A turn of the subspace by θ
    # changes G by at most 2 sin θ ‖K'_q P‖ ‖K'_q (I − P)‖, i.e. by
    # 2 sin θ · q_out relative to ‖G‖, q_out the reference's ratio of
    # query energy outside the subspace to inside it; the f32 rounding of
    # the served rows adds the model's relative scale.  θ is the
    # measured angle, which the angle check holds to its own bound: the
    # query check then tests the serving path given the state, and does
    # not pass a query error the state's own subspace cannot explain.
    query = (2.0 * math.sin(angle) * ref["q_out"]
             + SAFETY * EPS32 * math.sqrt(n_updates + 1))
    # The served rows against the float64 projection of the same state:
    # one f32 kernel row and one f32 dot (f32 accumulation) per entry,
    # so the relative error is O(eps) whatever the stream did before.
    serve = SAFETY * EPS32
    return {"eig": eig, "angle": angle_tol, "query": query, "serve": serve}


def check_window(tag, errs, tol, orth, healthy, heals):
    check(errs["eig_rel_err"] <= tol["eig"],
          f"{tag}: eigenvalue error {errs['eig_rel_err']:.3e} > "
          f"{tol['eig']:.3e}")
    check(errs["max_angle"] <= tol["angle"],
          f"{tag}: principal angle {errs['max_angle']:.3e} > "
          f"{tol['angle']:.3e}")
    check(errs["query_err"] <= tol["query"],
          f"{tag}: query error {errs['query_err']:.3e} > "
          f"{tol['query']:.3e}")
    check(errs["serve_err"] <= tol["serve"],
          f"{tag}: served projections {errs['serve_err']:.3e} from the "
          f"float64 ones > {tol['serve']:.3e}")
    # The orthogonality residual max_j ‖(UᵀU − I) e_j‖ over all columns
    # against the health policy's own threshold; the service's in-graph
    # probe (the same residual, negativity, finiteness) must agree, and
    # nothing may have healed along the way.
    from repro.core import health as hl

    check(orth <= hl.DEFAULT_POLICY.orth_tol,
          f"{tag}: orthogonality residual {orth:.3e} > orth_tol "
          f"{hl.DEFAULT_POLICY.orth_tol:.3e}")
    check(healthy, f"{tag}: the health probe fails the policy")
    check(heals == 0, f"{tag}: {heals} heal(s) on a clean stream")


def kernel_routes() -> dict:
    """{(kernel, route): count} of the dispatch decisions in the hub."""
    from repro import obs

    out = {}
    prefix = "kernel_dispatch_total{"
    for key, value in obs.get_hub().scrape().items():
        if key.startswith(prefix):
            labels = dict(kv.split("=") for kv in key[len(prefix):-1]
                          .replace('"', "").split(","))
            out[(labels["kernel"], labels["route"])] = int(value)
    return out


def check_routes(tag, routes, expect_route, required):
    seen = {k for k, _ in routes}
    check(required <= seen,
          f"{tag}: kernels {sorted(required - seen)} never dispatched")
    wrong = {k: r for k, r in routes if r != expect_route}
    check(not wrong, f"{tag}: kernel routes {wrong} are not {expect_route!r}")


def kernel_plan(**kw):
    from repro.core import engine as eng

    return eng.UpdatePlan(matmul="pallas2", fuse_krow=True,
                          dispatch="bucketed", **kw)


# ----------------------------------------------------------------- phase A --
def phase_window(x, *, plan_name, capacity, window, steps, every,
                 expect_route="pallas", clock=None, seed=0, refs=None):
    """A: one windowed stream, seeded at full window, ``steps`` more
    points in blocks of ``every`` with a query batch after each block.
    ``refs`` (a dict) keeps the float64 reference for a later run of the
    same stream and queries under another plan."""
    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.core import engine as eng, health as hl, inkpca
    from repro.core import kernels_fn as kf

    d = x.shape[1]
    sigma = float(d)
    spec = kf.KernelSpec(name="rbf", sigma=sigma)
    extra = dict(window=window, health=hl.DEFAULT_POLICY)
    plan = (kernel_plan(**extra) if plan_name == "kernel"
            else eng.UpdatePlan(dispatch="bucketed", **extra))
    x32 = x.astype(np.float32)
    rng = np.random.default_rng(seed)
    held = x32[window + steps:]
    obs.fresh_hub()
    c0 = _clock0(clock)
    t0 = time.perf_counter()
    stream = inkpca.KPCAStream(jnp.asarray(x32[:window]), capacity, spec,
                               adjusted=True, plan=plan, dtype=jnp.float32)
    jax.block_until_ready(stream.kpca_state.U)
    init_s = time.perf_counter() - t0
    blocks, queries, heals = [], [], 0
    n_comp = min(N_COMP, window - 1)
    for b in range(steps // every):
        xs = jnp.asarray(x32[window + b * every:window + (b + 1) * every])
        t = time.perf_counter()
        stream.update_block(xs)
        jax.block_until_ready(stream.kpca_state.U)
        blocks.append(time.perf_counter() - t)
        if not stream.is_healthy():
            stream.heal()
            heals += 1
        xq = held[rng.choice(held.shape[0], QUERY_BATCH, replace=False)]
        t = time.perf_counter()
        y = stream.transform(jnp.asarray(xq), n_components=n_comp)
        jax.block_until_ready(y)
        queries.append(time.perf_counter() - t)
    state = stream.kpca_state
    compile_s = _compile_s(clock, c0)
    routes = kernel_routes()
    orth = hl.exact_orth_residual(state)
    healthy = stream.is_healthy()

    # The FIFO ring keeps the survivors in arrival order, so physical row
    # i holds the i-th oldest point of the trailing window.
    xw = x[steps:window + steps]
    check(np.array_equal(np.asarray(state.X[:window]), xw.astype(np.float32)),
          f"A/{plan_name}: stored points are not the trailing window")
    refs = {} if refs is None else refs
    key = (window, steps, seed)
    if key not in refs:
        refs[key] = window_reference(xw, xq.astype(np.float64), sigma,
                                     n_comp)
    ref = refs[key]
    errs = compare_window(state, y, ref, n_comp)
    tol = window_tolerances(ref, 8 * steps, n_comp, errs["max_angle"])
    out = {"phase": f"A/{plan_name}", "M": capacity, "W": window,
           "points": steps, **errs,
           "tol": {k: round(v, 9) for k, v in tol.items()},
           "q_out": ref["q_out"],
           "orth_residual": orth,
           "orth_tol": hl.DEFAULT_POLICY.orth_tol, "healthy": healthy,
           "heals": heals,
           "routes": {f"{k}:{r}": n for (k, r), n in sorted(routes.items())},
           "init_s": round(init_s, 3), "first_block_s": round(blocks[0], 3),
           "steady_block_s": round(float(np.median(blocks[1:] or blocks)),
                                   3),
           "first_query_s": round(queries[0], 4),
           "steady_query_s": round(float(np.median(queries[1:] or queries)),
                                   4),
           "compile_s": compile_s}
    print(json.dumps(out), flush=True)
    check_window(out["phase"], errs, tol, orth, healthy, heals)
    if plan_name == "kernel":
        check_routes(out["phase"], routes, expect_route,
                     {"eigvec_rotate2", "eigvec_project", "krow_project",
                      "transform_project"})
    return out


# ----------------------------------------------------------------- phase B --
def phase_tenants(x, *, tenants, capacity, window, steps, query_rate,
                  expect_route="pallas", clock=None, seed=0):
    """B: the decoupled multi-tenant service, tenant t streaming its own
    contiguous slice of the data; tenant 0 is checked against a batch
    reference of its trailing window."""
    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.core import engine as eng, health as hl, kernels_fn as kf
    from repro.launch.serve import IngestServeLoop

    d = x.shape[1]
    sigma = float(d)
    spec = kf.KernelSpec(name="rbf", sigma=sigma)
    plan = kernel_plan(window=window, serve_every=1,
                       serve_components=N_COMP, health=hl.DEFAULT_POLICY)
    per = window + steps
    x32 = x.astype(np.float32)
    streams = x32[:tenants * per].reshape(tenants, per, d)
    held = x32[tenants * per:]
    rng = np.random.default_rng(seed)
    obs.fresh_hub()
    c0 = _clock0(clock)
    t0 = time.perf_counter()
    batch = eng.StreamBatch(jnp.asarray(streams[:, :window]), capacity, spec,
                            plan=plan, adjusted=True, dtype=jnp.float32,
                            window=window)
    loop = IngestServeLoop(batch, spec, plan=plan, n_components=N_COMP)
    jax.block_until_ready(loop.snaps.S)
    init_s = time.perf_counter() - t0
    step_s, served = [], 0
    for t in range(steps):
        t1 = time.perf_counter()
        for _ in range(query_rate):
            xq = held[rng.choice(held.shape[0], (tenants, QUERY_BATCH))]
            jax.block_until_ready(loop.query(jnp.asarray(xq)))
            served += tenants * QUERY_BATCH
        published = loop.ingest(jnp.asarray(streams[:, window + t]))
        jax.block_until_ready(loop.snaps.S)
        step_s.append(time.perf_counter() - t1)
        check(published, f"B: step {t} did not publish")
    xq = held[rng.choice(held.shape[0], QUERY_BATCH, replace=False)]
    y = loop.query(jnp.asarray(np.broadcast_to(xq, (tenants,) + xq.shape)))
    state0 = batch.state_of(0)
    compile_s = _compile_s(clock, c0)
    routes = kernel_routes()
    orth = hl.exact_orth_residual(state0)
    healthy = bool(np.all(np.asarray(batch.probe_all()[0])))
    xw = streams[0, steps:per].astype(np.float64)
    ref = window_reference(xw, xq.astype(np.float64), sigma, N_COMP)
    errs = compare_window(state0, y[0], ref, N_COMP)
    tol = window_tolerances(ref, 8 * steps, N_COMP, errs["max_angle"])
    out = {"phase": "B", "tenants": tenants, "M": capacity, "W": window,
           "steps": steps, **errs,
           "tol": {k: round(v, 9) for k, v in tol.items()},
           "q_out": ref["q_out"],
           "orth_residual_t0": orth,
           "orth_tol": hl.DEFAULT_POLICY.orth_tol, "healthy": healthy,
           "heals": loop.heals,
           "skipped_publishes": loop.skipped, "generations": loop.generation,
           "queries_served": served,
           "routes": {f"{k}:{r}": n for (k, r), n in sorted(routes.items())},
           "init_s": round(init_s, 3), "first_step_s": round(step_s[0], 3),
           "steady_step_s": round(float(np.median(step_s[1:] or step_s)), 3),
           "compile_s": compile_s}
    print(json.dumps(out), flush=True)
    check(loop.skipped == 0, f"B: {loop.skipped} skipped publish(es)")
    check(loop.generation == steps,
          f"B: {loop.generation} publishes for {steps} steps")
    check_window("B/tenant0", errs, tol, orth, healthy, loop.heals)
    check_routes("B", routes, expect_route,
                 {"eigvec_rotate2", "krow_project", "transform_project"})
    return out


# ----------------------------------------------------------------- phase C --
def nystrom_reference(landmarks, rows, sigma):
    """Exact trace error of the Nyström approximation on ``rows`` with
    these landmarks, float64: Σ_i k(x_i, x_i) − Σ_i (K_nm K_mm⁻¹ K_mn)_ii,
    and the condition number of K_mm."""
    Kmm = rbf(landmarks, landmarks, sigma)
    Knm = rbf(rows, landmarks, sigma)
    w, v = np.linalg.eigh(Kmm)
    B = Knm @ v
    return (float(rows.shape[0] - np.sum(B * B / w[None, :])),
            float(w[-1] / w[0]))


def phase_nystrom(x, *, capacity, clock=None):
    """C: the leverage landmark lifecycle over the stream ``x``."""
    import jax.numpy as jnp

    from repro import obs
    from repro.core import engine as eng, kernels_fn as kf, nystrom
    from repro.launch.serve import nystrom_lifecycle

    d = x.shape[1]
    sigma = float(d)
    spec = kf.KernelSpec(name="rbf", sigma=sigma)
    plan = eng.UpdatePlan(dispatch="bucketed", landmark_policy="leverage")
    engine = eng.Engine(spec, plan, adjusted=False)
    x32 = x.astype(np.float32)
    hub = obs.fresh_hub()
    c0 = _clock0(clock)
    t0 = time.perf_counter()
    state = nystrom.init_nystrom(None, jnp.asarray(x32[:4]), capacity, spec,
                                 grow_rows=True)
    rule = nystrom.SufficientSubsetRule()
    run = nystrom_lifecycle(engine, state, jnp.asarray(x32[4:]),
                            budget=capacity - 1, rule=rule, hub=hub)
    state, tracker = run["state"], run["tracker"]
    err = float(nystrom.trace_error(state, spec))
    seconds = time.perf_counter() - t0
    m = int(state.kpca.m)
    landmarks = np.asarray(state.kpca.X[:m], np.float64)
    rows = np.asarray(state.Xrows, np.float64)
    ref, cond = nystrom_reference(landmarks, rows, sigma)
    n_t = run["tracker_rows"]
    ref_t, _ = nystrom_reference(landmarks, rows[:n_t], sigma)
    # trace_error is an f32 sum over the n rows of k_ii − K̃_ii ≥ 0.  A
    # reduction tree of depth log2(n) rounds it by at most
    # eps·log2(n)·Σ|terms| = eps·log2(n)·T (Higham's pairwise-summation
    # bound); the factor 4 covers the f32 rounding of each K̃_ii, which
    # passes through K_mm⁻¹ but largely cancels over the rows.
    n = rows.shape[0]
    tol = 4.0 * EPS32 * math.log2(n) * ref
    # The tracker adds one f32 residual k(x,x) − bᵀK_mm⁺b per tracked row
    # (a difference of two numbers of size k(x,x), so eps·k(x,x) each),
    # plus the admission deltas, accumulated one after the other.
    tol_t = SAFETY * EPS32 * n_t
    out = {"phase": "C", "capacity": capacity, "rows": int(rows.shape[0]),
           "landmarks": m, "stopped_at": run["stopped_at"], **run["counts"],
           "trace_error": err, "reference": ref,
           "trace_error_err": abs(err - ref), "tol": tol,
           "tracker": tracker.value, "tracker_reference": ref_t,
           "tracker_rows": n_t, "tracker_err": abs(tracker.value - ref_t),
           "tracker_tol": tol_t, "cond_Kmm": cond,
           "seconds": round(seconds, 3), "compile_s": _compile_s(clock, c0)}
    print(json.dumps(out), flush=True)
    check(abs(err - ref) <= tol,
          f"C: trace_error {err} is {abs(err - ref):.3e} from the float64 "
          f"recompute {ref} (> {tol:.3e})")
    check(abs(tracker.value - ref_t) <= tol_t,
          f"C: tracker {tracker.value} is {abs(tracker.value - ref_t):.3e} "
          f"from the float64 recompute {ref_t} (> {tol_t:.3e})")
    return out


# ------------------------------------------------------------ four chips --
def phase_sharded(x, *, capacity, window, steps, devices, clock=None):
    """The row-sharded window step (``distributed.make_sharded_window_
    block``, unadjusted) on a ``data`` mesh over ``devices`` against the
    single-device windowed engine on the same stream."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import obs
    from repro.core import distributed as dist, engine as eng, inkpca
    from repro.core import kernels_fn as kf
    from repro.distributed.sharding import make_mesh

    d = x.shape[1]
    spec = kf.KernelSpec(name="rbf", sigma=float(d))
    plan = kernel_plan(window=window)
    x32 = x.astype(np.float32)
    obs.fresh_hub()
    c0 = _clock0(clock)
    stream = inkpca.KPCAStream(jnp.asarray(x32[:window]), capacity, spec,
                               adjusted=False, plan=plan, dtype=jnp.float32)
    ws = stream.state
    mesh = make_mesh((len(devices),), ("data",), devices=devices)
    U = jax.device_put(ws.kpca.U, NamedSharding(mesh, P("data", None)))
    rep = NamedSharding(mesh, P())
    L, X, ages, clock_, m = (jax.device_put(a, rep) for a in (
        ws.kpca.L, ws.kpca.X, ws.ages, ws.clock, ws.kpca.m))
    placed = sorted((s.device.id, tuple(s.data.shape))
                    for s in U.addressable_shards)
    block = dist.make_sharded_window_block(mesh, spec, plan=plan)
    xs = jnp.asarray(x32[window:window + steps])
    t0 = time.perf_counter()
    L, U, X, ages, clock_ = block(L, U, X, ages, clock_, jax.device_put(
        xs, rep), m)
    jax.block_until_ready(U)
    sharded_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    stream.update_block(xs)
    jax.block_until_ready(stream.kpca_state.U)
    local_s = time.perf_counter() - t0
    local = stream.kpca_state
    sharded = local._replace(L=jax.device_get(L), U=jax.device_get(U),
                             X=jax.device_get(X))
    # Both runs against the float64 reference of the raw (unadjusted)
    # gram of the trailing window, under the window model's bounds; the
    # direct difference between them is printed as well.
    k = N_COMP
    ref = window_reference(x[steps:window + steps], None, float(d), k,
                           centred=False)
    tol = window_tolerances(ref, 4 * steps, k)
    err_l = compare_window(local, None, ref, k)
    err_s = compare_window(sharded, None, ref, k)
    lam_l = np.asarray(eng.eigpairs(local)[0][:k], np.float64)
    lam_s = np.asarray(eng.eigpairs(sharded)[0][:k], np.float64)
    routes = kernel_routes()
    out = {"phase": f"sharded/M={capacity}", "devices": len(devices),
           "placed": placed, "steps": steps, "sharded": err_s,
           "local": err_l, "eig_rel_diff": float(np.max(
               np.abs(lam_s - lam_l) / lam_l)),
           "tol": {"eig": tol["eig"], "angle": tol["angle"]},
           "same_ring": bool(np.array_equal(np.asarray(ages),
                                            np.asarray(stream.state.ages))),
           "routes": {f"{k}:{r}": n for (k, r), n in sorted(routes.items())},
           "sharded_s": round(sharded_s, 3), "local_s": round(local_s, 3),
           "compile_s": _compile_s(clock, c0)}
    print(json.dumps(out), flush=True)
    check(len({dev for dev, _ in placed}) == len(devices)
          and all(shape[0] == capacity // len(devices)
                  for _, shape in placed),
          f"sharded: U is not split over {len(devices)} devices: {placed}")
    check(out["same_ring"], "sharded: arrival rings differ")
    for tag, e in (("sharded", err_s), ("local", err_l)):
        check(e["eig_rel_err"] <= tol["eig"],
              f"{tag}: eigenvalue error {e['eig_rel_err']:.3e} > "
              f"{tol['eig']:.3e}")
        check(e["max_angle"] <= tol["angle"],
              f"{tag}: principal angle {e['max_angle']:.3e} > "
              f"{tol['angle']:.3e}")
    return out


# -------------------------------------------------------------------- main --
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the Magic-like stream and the queries")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the row-sharded window step on four "
                         "chips and the single-device engine it is "
                         "compared with")
    args = ap.parse_args(argv)

    import jax

    from repro.data.uci_like import load_dataset
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform} devices)",
              file=sys.stderr)
        return 2
    if os.environ.get("REPRO_PALLAS_FORCE"):
        print("chip_smoke: REPRO_PALLAS_FORCE is set; the kernel routes "
              "must be chosen by the platform", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    # Any silent narrowing of a requested 64-bit dtype is a failure here.
    warnings.filterwarnings("error", message=".*Explicitly requested dtype")
    cache = enable_compile_cache()
    clock = CompileClock()
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} cache={cache}", flush=True)

    x = load_dataset("magic", seed=args.seed)
    t_all = time.perf_counter()
    if args.four_chips:
        phases = [lambda: phase_sharded(x, capacity=8192, window=8192,
                                        steps=16, devices=devices[:4],
                                        clock=clock)]
    else:
        refs: dict = {}
        phases = [
            lambda: phase_window(x, plan_name="default", capacity=8192,
                                 window=8192, steps=64, every=16,
                                 clock=clock, seed=args.seed, refs=refs),
            lambda: phase_window(x, plan_name="kernel", capacity=8192,
                                 window=8192, steps=64, every=16,
                                 clock=clock, seed=args.seed, refs=refs),
            lambda: phase_tenants(x, tenants=8, capacity=2048, window=2048,
                                  steps=8, query_rate=4, clock=clock,
                                  seed=args.seed),
            lambda: phase_nystrom(x, capacity=512, clock=clock),
        ]
    failures = []
    for run in phases:
        try:
            run()
        except SmokeFailure as e:
            # Record and go on to the next phase; the exit code says it.
            print(f"chip_smoke: FAILED {e}", file=sys.stderr, flush=True)
            failures.append(str(e))
    print(f"[total] seconds={time.perf_counter() - t_all:.3f} "
          f"compile_s={clock.seconds:.3f} failures={len(failures)}",
          flush=True)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
