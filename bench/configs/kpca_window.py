"""Plain reference of the windowed KPCA deployments: a float64 batch
kernel PCA of one tenant's trailing window, on the host.

It imports nothing of the program and takes nothing the program made:
its inputs are the window's points and the query rows, both made by the
benchmark from the seed.  The arithmetic is that of ``chip_smoke.py``'s
``window_reference`` (RBF k(x, y) = exp(-|x - y|² / sigma), the centred
gram (I - 1/n) K (I - 1/n), a query's centred row
k_q - mean(k_q) - mean_rows(K) + mean(K)).
"""
from __future__ import annotations

import numpy as np


def rbf(x, y, sigma):
    """k(x, y) = exp(-|x - y|² / sigma) in float64."""
    d2 = (np.sum(x * x, 1)[:, None] + np.sum(y * y, 1)[None, :]
          - 2.0 * (x @ y.T))
    return np.exp(-np.maximum(d2, 0.0) / sigma)


def window_reference(xw, queries, sigma, k):
    """Top-(k+1) eigenpairs of the centred gram of the window ``xw``
    (W, d), and for each (nq, d) array in ``queries`` the projected query
    gram G = K'_q P K'_qᵀ on the top-k subspace (P its projector), which
    no sign or rotation of eigenvectors inside the subspace changes."""
    from scipy.sparse.linalg import eigsh

    xw = np.asarray(xw, np.float64)
    K = rbf(xw, xw, sigma)
    r = K.mean(axis=1)
    mu = r.mean()
    Kc = K - r[:, None] - r[None, :] + mu
    del K
    # A fixed start vector, so the reference is the same on every run.
    v0 = np.random.default_rng(0).standard_normal(xw.shape[0])
    lam, vec = eigsh(Kc, k=k + 1, which="LA", v0=v0)
    order = np.argsort(lam)[::-1]
    lam, vec = lam[order], vec[:, order]
    G = []
    for xq in queries:
        Kq = rbf(np.asarray(xq, np.float64), xw, sigma)
        Kqc = Kq - Kq.mean(axis=1, keepdims=True) - r[None, :] + mu
        P = Kqc @ vec[:, :k]
        G.append(P @ P.T)
    return {"lam": lam, "U": vec[:, :k], "G": G}
