"""The comparison that decides ``correct``.

After the window has closed and the device's peak memory has been read,
the service's state and what it served are compared with the
configuration's plain float64 reference (``configs/<reference>.py``),
tenant by tenant, at the timed sizes.  The arithmetic of the comparison
is that of ``chip_smoke.py``'s ``compare_window``, read from the raw
arrays rather than through the program's helpers.

Numbers compared, each against the cell's limit in
``bench/limits/<cell>.json`` (``orth`` against the health policy's
``orth_tol``, which the configuration states):

- ``rows_off``: stored points of the tenants that are not their trailing
  window (exact, limit 0);
- ``eig_err``: largest relative error of the top-k eigenvalues of the
  working states after the window, every tenant;
- ``orth``: largest column residual max_j ‖(UᵀU − I) e_j‖ of a working
  state's eigenvectors;
- ``snap_eig_err``: the same error for the eigenvalues the last published
  snapshot serves with (its projection matrix S has columns u_i/sqrt(λ_i),
  so λ_i = 1/‖S e_i‖²), against the window it holds;
- ``query_err``: for a seeded sample of the queries served in the
  window, one seeded tenant each, the relative Frobenius error of
  Y Λ Yᵀ (Y the served rows, Λ the reference's top-k eigenvalues)
  against the reference's K'_q P K'_qᵀ of the window that the snapshot
  the query read held;
- ``unpublished_steps``: ingest steps (warm-up included) that the
  configuration's publish cadence (``serve_every``) should have published
  and did not (limit 0): every point is folded into a published snapshot;
- ``skipped_publishes``: publishes the health gate refused (limit 0);
- ``heals``: tenants the health gate sent down the heal ladder (limit 0):
  a heal rebuilds the eigensystem from the stored points, so it would
  hide an update that went wrong;
- ``compiles``: compile events inside the window and its drain (limit 0).
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def load_reference(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{name}", CONFIGS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def top_eigenvalues(L, m, k):
    """Descending top-k of one tenant's active eigenvalues, float64."""
    return np.sort(np.asarray(L[:m], np.float64))[::-1][:k]


def orth_residual(U, m) -> float:
    """max_j ‖(UᵀU − I) e_j‖₂ over the active columns (float32 BLAS: its
    own rounding, ~sqrt(m)·eps, is far below any limit on this number)."""
    Um = np.asarray(U[:m, :m], np.float32)
    G = Um.T @ Um
    G[np.diag_indices(m)] -= 1.0
    return float(np.sqrt(np.max(np.sum(G.astype(np.float64) ** 2, 0))))


def query_error(y, lam_ref, G_ref) -> float:
    y = np.asarray(y, np.float64)
    G = (y * lam_ref[None, :]) @ y.T
    return float(np.linalg.norm(G - G_ref) / np.linalg.norm(G_ref))


def compare(svc, w, compile_events: int, seed: int, lim: dict) -> dict:
    """{name: (value, limit)} of one run; ``svc`` after the window."""
    cfg = svc.cfg
    ref_mod = load_reference(cfg["reference"])
    k, W, sigma = cfg["serve_components"], svc.W, float(cfg["sigma"])
    streams = svc.streams
    st = svc.batch.states
    L, U = np.asarray(st.L), np.asarray(st.U)
    m, X = np.asarray(st.m), np.asarray(st.X)
    snaps = svc.loop.snaps
    S_snap, m_snap = np.asarray(snaps.S), np.asarray(snaps.m)
    steps, pub = svc.steps, svc.published
    rng = np.random.default_rng([seed, 3])
    # Which reference windows are needed: every tenant's after the
    # window; each sampled query's tenant at the steps its snapshot held.
    sampled = sorted(w.q_kept.items())
    q_tenant = {i: int(rng.integers(svc.B)) for i, _ in sampled}
    need: dict = {}
    for t in range(svc.B):
        need.setdefault((t, steps), [])
        need.setdefault((t, pub), [])
    for i, (q, _, p) in sampled:
        need.setdefault((q_tenant[i], p), []).append(i)
    refs = {}
    for (t, s), qs in need.items():
        xw = streams[t, s:s + W]
        refs[(t, s)] = (ref_mod.window_reference(
            xw, [w.q_kept[i][0][q_tenant[i]] for i in qs], sigma, k),
            {i: j for j, i in enumerate(qs)})

    rows_off = eig = orth = snap = 0.0
    x32 = streams.astype(np.float32)
    for t in range(svc.B):
        mt = int(m[t])
        rows_off += int(np.sum(np.any(X[t, :mt] != x32[t, steps:steps + mt],
                                      axis=1))) + abs(mt - W)
        ref = refs[(t, steps)][0]
        lam = top_eigenvalues(L[t], mt, k)
        eig = max(eig, float(np.max(np.abs(lam - ref["lam"][:k])
                                    / ref["lam"][:k])))
        orth = max(orth, orth_residual(U[t], mt))
        ms = int(m_snap[t])
        lam_snap = 1.0 / np.sum(np.asarray(S_snap[t, :ms], np.float64) ** 2,
                                axis=0)
        lam_pub = refs[(t, pub)][0]["lam"][:k]
        snap = max(snap, float(np.max(np.abs(lam_snap - lam_pub)
                                      / lam_pub)))
    qerr = 0.0
    for i, (q, y, p) in sampled:
        t = q_tenant[i]
        ref, pos = refs[(t, p)]
        qerr = max(qerr, query_error(y[t], ref["lam"][:k],
                                     ref["G"][pos[i]]))
    out = {"rows_off": (float(rows_off), 0.0),
           "eig_err": (eig, lim["eig_err"]),
           "orth": (orth, float(cfg["orth_tol"])),
           "snap_eig_err": (snap, lim["snap_eig_err"])}
    if sampled:
        out["query_err"] = (qerr, lim["query_err"])
    due = svc.steps // int(cfg["serve_every"])
    out["unpublished_steps"] = (float(max(due - svc.publishes, 0)), 0.0)
    out["skipped_publishes"] = (float(svc.loop.skipped), 0.0)
    out["heals"] = (float(svc.loop.heals), 0.0)
    out["compiles"] = (float(compile_events), 0.0)
    return out


def verdict(numbers: dict) -> bool:
    return all(v <= lim for v, lim in numbers.values())
