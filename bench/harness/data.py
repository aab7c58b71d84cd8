"""The Magic-like stream, a copy of the program's ``data/uci_like`` stand-in.

The UCI Magic gamma telescope file (n=19 020, d=10) is not in the
repository, so the deployment streams a seeded stand-in of the same
shape: a two-cluster mixture of anisotropic Gaussians with heavy tails on
three features, standardised.  Kept here so that the benchmark's data
cannot change with the program.
"""
from __future__ import annotations

import numpy as np


def _rand_cov(rng, d: int, scale: float = 1.0) -> np.ndarray:
    a = rng.normal(size=(d, d))
    cov = a @ a.T / d
    # exponentially decaying eigenvalue profile (correlated features)
    _, v = np.linalg.eigh(cov)
    w = scale * np.exp(-np.arange(d)[::-1] / 2.5)
    return (v * w) @ v.T


def magic_like(n: int, d: int, seed: int) -> np.ndarray:
    """``n`` standardised float64 points of the Magic-like mixture."""
    rng = np.random.default_rng(seed)
    n1 = int(n * 0.65)
    cov1 = _rand_cov(rng, d, scale=2.0)
    cov2 = _rand_cov(rng, d, scale=3.0)
    x1 = rng.multivariate_normal(np.zeros(d), cov1, size=n1)
    x2 = rng.multivariate_normal(rng.normal(0, 1.5, d), cov2, size=n - n1)
    x = np.concatenate([x1, x2], axis=0)
    # heavy tails on a few features, as in the telescope shower statistics
    x[:, :3] = np.sign(x[:, :3]) * np.abs(x[:, :3]) ** 1.5
    rng.shuffle(x)
    return (x - x.mean(0)) / np.maximum(x.std(0), 1e-9)


DATASETS = {"magic_like": magic_like}


def tenant_streams(cfg: dict, seed: int) -> np.ndarray:
    """(tenants, n, d) float64: tenant t streams its own seeded sample."""
    make = DATASETS[cfg["dataset"]]
    ss = np.random.SeedSequence(seed)
    return np.stack([make(cfg["n"], cfg["d"], int(s.generate_state(1)[0]))
                     for s in ss.spawn(cfg["tenants"])])
