"""Unit + property tests for the rank-one eigendecomposition update (§3.2).

The property tests need ``hypothesis``; when it is absent (the container
does not ship it) they are skipped via no-op decorator stand-ins so the
deterministic tests still collect and run.
"""
import numpy as np
import jax.numpy as jnp
import jax
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # pragma: no cover - exercised in the container
    HAVE_HYPOTHESIS = False

    def given(**kwargs):
        def deco(fn):
            return pytest.mark.skip(reason="hypothesis not installed")(fn)
        return deco

    def settings(**kwargs):
        def deco(fn):
            return fn
        return deco

    class _St:
        """Stand-in for hypothesis.strategies; decorators skip anyway."""

        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _St()

from repro.core import rankone

RNG = np.random.default_rng(0)


def _padded_eigensystem(m, M, scale=1.0):
    A = RNG.normal(size=(m, m)) * scale
    A = A @ A.T
    lam, vec = np.linalg.eigh(A)
    L = np.zeros(M)
    U = np.eye(M)
    L[:m] = lam
    U[:m, :m] = vec
    L = rankone.sentinelize(jnp.asarray(L), jnp.int32(m), jnp.float64(0.0))
    return A, jnp.asarray(L), jnp.asarray(U)


@pytest.mark.parametrize("sigma", [0.5, -0.5, 4.0, -4.0])
@pytest.mark.parametrize("m,M", [(6, 8), (10, 10), (17, 32)])
def test_rank_one_update_matches_eigh(sigma, m, M):
    A, L, U = _padded_eigensystem(m, M)
    v = np.zeros(M)
    v[:m] = RNG.normal(size=m)
    L2, U2 = rankone.rank_one_update(L, U, jnp.asarray(v),
                                     jnp.float64(sigma), jnp.int32(m))
    B = A + sigma * np.outer(v[:m], v[:m])
    lam_ref = np.linalg.eigh(B)[0]
    np.testing.assert_allclose(np.sort(np.asarray(L2[:m])), lam_ref,
                               rtol=1e-9, atol=1e-9)
    rec = np.asarray(rankone.reconstruct(L2, U2, jnp.int32(m)))[:m, :m]
    np.testing.assert_allclose(rec, B, rtol=1e-8, atol=1e-8)


@pytest.mark.parametrize("method", ["gu", "bns"])
def test_orthogonality_after_update(method):
    m, M = 12, 16
    _, L, U = _padded_eigensystem(m, M)
    v = np.zeros(M)
    v[:m] = RNG.normal(size=m)
    L2, U2 = rankone.rank_one_update(L, U, jnp.asarray(v), jnp.float64(1.3),
                                     jnp.int32(m), method=method)
    G = np.asarray(U2[:m, :m]).T @ np.asarray(U2[:m, :m])
    assert np.abs(G - np.eye(m)).max() < 1e-8


@settings(max_examples=25, deadline=None)
@given(m=st.integers(3, 12), sigma=st.floats(-5.0, 5.0),
       seed=st.integers(0, 10_000))
def test_interlacing_bounds(m, sigma, seed):
    """Paper eq. (5): updated eigenvalues interlace the old ones."""
    if abs(sigma) < 1e-3:
        sigma = 1e-3
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(m, m))
    A = A @ A.T
    lam, vec = np.linalg.eigh(A)
    v = rng.normal(size=m)
    z = vec.T @ v
    lam2 = np.linalg.eigh(A + sigma * np.outer(v, v))[0]
    tol = 1e-8 * max(1.0, np.abs(lam).max())
    if sigma > 0:
        for i in range(m - 1):
            assert lam[i] - tol <= lam2[i] <= lam[i + 1] + tol
        assert lam[-1] - tol <= lam2[-1] <= lam[-1] + sigma * z @ z + tol
    else:
        for i in range(1, m):
            assert lam[i - 1] - tol <= lam2[i] <= lam[i] + tol
        assert lam[0] + sigma * z @ z - tol <= lam2[0] <= lam[0] + tol


@settings(max_examples=20, deadline=None)
@given(m=st.integers(2, 10), seed=st.integers(0, 10_000),
       sigma=st.sampled_from([0.7, -0.7, 2.5, -2.5]))
def test_update_matches_eigh_property(m, seed, sigma):
    rng = np.random.default_rng(seed)
    M = m + rng.integers(0, 4)
    A = rng.normal(size=(m, m))
    A = A @ A.T
    lam, vec = np.linalg.eigh(A)
    L = np.zeros(M); U = np.eye(M)
    L[:m] = lam; U[:m, :m] = vec
    L = rankone.sentinelize(jnp.asarray(L), jnp.int32(m), jnp.float64(0.0))
    v = np.zeros(M); v[:m] = rng.normal(size=m)
    L2, _ = rankone.rank_one_update(jnp.asarray(L), jnp.asarray(U),
                                    jnp.asarray(v), jnp.float64(sigma),
                                    jnp.int32(m))
    lam_ref = np.linalg.eigh(A + sigma * np.outer(v[:m], v[:m]))[0]
    np.testing.assert_allclose(np.sort(np.asarray(L2[:m])), lam_ref,
                               rtol=1e-7, atol=1e-7)


def test_expand_eigensystem():
    m, M = 5, 8
    A, L, U = _padded_eigensystem(m, M)
    L2, U2, m2 = rankone.expand_eigensystem(L, U, jnp.float64(0.33),
                                            jnp.int32(m))
    assert int(m2) == m + 1
    rec = np.asarray(rankone.reconstruct(L2, U2, m2))[:m + 1, :m + 1]
    ref = np.zeros((m + 1, m + 1))
    ref[:m, :m] = A
    ref[m, m] = 0.33
    np.testing.assert_allclose(rec, ref, atol=1e-10)


def test_deflation_clamp_tiny_z():
    """v orthogonal to U's range (z ~ 0) must not produce NaNs."""
    m, M = 6, 8
    _, L, U = _padded_eigensystem(m, M)
    v = np.zeros(M)  # exactly zero update
    L2, U2 = rankone.rank_one_update(L, U, jnp.asarray(v), jnp.float64(2.0),
                                     jnp.int32(m))
    assert np.isfinite(np.asarray(L2)).all()
    assert np.isfinite(np.asarray(U2)).all()


def test_sentinelize_keeps_active_sorted_top():
    L = jnp.asarray([3.0, 1.0, 0.0, 0.0])
    Ls = rankone.sentinelize(L, jnp.int32(2), jnp.float64(0.0))
    assert float(Ls[2]) > 3.0 and float(Ls[3]) > float(Ls[2])


# --------------------------------------------- fused-pair merge fallback ---
def _clustered_eigensystem(m, M, n_cluster, seed, width=1e-14):
    """Eigensystem with a near-degenerate cluster (dlaed2 territory)."""
    rng = np.random.default_rng(seed)
    lam = np.sort(np.concatenate([
        2.0 + rng.normal(size=n_cluster) * width,
        rng.uniform(3.0, 6.0, size=m - n_cluster)]))
    vec, _ = np.linalg.qr(rng.normal(size=(m, m)))
    L = np.zeros(M)
    U = np.eye(M)
    L[:m] = lam
    U[:m, :m] = vec
    L = rankone.sentinelize(jnp.asarray(L), jnp.int32(m), jnp.float64(0.0))
    v1 = np.zeros(M)
    v2 = np.zeros(M)
    v1[:m] = rng.normal(size=m)
    v2[:m] = rng.normal(size=m)
    return jnp.asarray(L), jnp.asarray(U), jnp.asarray(v1), jnp.asarray(v2)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("sigma", [1.3, -0.8])
def test_pair_merge_fallback_on_clustered_spectrum(seed, sigma):
    """When a dlaed2 cluster-merge fires, the fused pair must cond into the
    sequential two-update path (ROADMAP follow-up): eigenvalues AND
    orthogonality must match two rank_one_update calls exactly."""
    m, M = 10, 16
    L, U, v1, v2 = _clustered_eigensystem(m, M, n_cluster=4, seed=seed)
    z1 = U.T @ v1
    assert bool(rankone._merge_fires(L, z1, jnp.float64(sigma),
                                     jnp.int32(m)))

    Ls, Us = rankone.rank_one_update(L, U, v1, jnp.float64(sigma),
                                     jnp.int32(m))
    Ls, Us = rankone.rank_one_update(Ls, Us, v2, jnp.float64(-sigma),
                                     jnp.int32(m))
    Lp, Up = rankone.rank_one_update_pair(L, U, v1, jnp.float64(sigma),
                                          v2, jnp.float64(-sigma),
                                          jnp.int32(m))
    np.testing.assert_allclose(np.asarray(Lp[:m]), np.asarray(Ls[:m]),
                               atol=1e-10)
    np.testing.assert_allclose(np.abs(np.asarray(Up[:m, :m])),
                               np.abs(np.asarray(Us[:m, :m])), atol=1e-10)
    G = np.asarray(Up[:m, :m]).T @ np.asarray(Up[:m, :m])
    assert np.abs(G - np.eye(m)).max() < 1e-9


def test_pair_no_fallback_on_clean_spectrum():
    """A well-separated spectrum must NOT trip the fallback (the fused
    rotation is the steady-state path)."""
    m, M = 10, 16
    _, L, U = _padded_eigensystem(m, M)
    v = np.zeros(M)
    v[:m] = RNG.normal(size=m)
    z = jnp.asarray(U).T @ jnp.asarray(v)
    assert not bool(rankone._merge_fires(jnp.asarray(L), z,
                                         jnp.float64(1.3), jnp.int32(m)))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), n_cluster=st.integers(2, 6),
       sigma=st.sampled_from([0.7, -0.7, 2.5]))
def test_pair_merge_fallback_property(seed, n_cluster, sigma):
    """Property form: for random near-degenerate spectra the fused pair
    (with fallback) always reproduces the sequential path and keeps the
    updated eigenvectors orthogonal."""
    m, M = 9, 12
    L, U, v1, v2 = _clustered_eigensystem(m, M, n_cluster=n_cluster,
                                          seed=seed,
                                          width=10.0 ** -np.random.default_rng(
                                              seed).integers(12, 16))
    Ls, Us = rankone.rank_one_update(L, U, v1, jnp.float64(sigma),
                                     jnp.int32(m))
    Ls, Us = rankone.rank_one_update(Ls, Us, v2, jnp.float64(-sigma),
                                     jnp.int32(m))
    Lp, Up = rankone.rank_one_update_pair(L, U, v1, jnp.float64(sigma),
                                          v2, jnp.float64(-sigma),
                                          jnp.int32(m))
    np.testing.assert_allclose(np.asarray(Lp[:m]), np.asarray(Ls[:m]),
                               atol=1e-9)
    G = np.asarray(Up[:m, :m]).T @ np.asarray(Up[:m, :m])
    assert np.abs(G - np.eye(m)).max() < 1e-8


def test_secular_roots_resolve_offsets_below_the_pole_ulp():
    """Each f32 root comes back as (nearer pole, offset): an offset far
    below the pole's rounding unit keeps its relative accuracy."""
    d = np.arange(8, dtype=np.float64) * 10.0 + 100.0    # ulp(100) ~ 8e-6
    z = np.full(8, 0.3)
    z[3] = 1e-3                                          # shift ~1e-6
    sigma = 1.0
    exact = np.linalg.eigvalsh(np.diag(d) + sigma * np.outer(z, z))
    org, tau = rankone._secular_bisect(
        jnp.asarray(d, jnp.float32), jnp.asarray(z * z, jnp.float32),
        jnp.float32(sigma), 32)
    org, tau = np.asarray(org, np.float64), np.asarray(tau, np.float64)
    # Offsets from the poles d_j, computed without ever forming the root.
    got = (org - d) + tau
    want = exact - d
    np.testing.assert_allclose(got, want, rtol=1e-4)
    assert abs(got[3]) < 1e-5


@pytest.mark.parametrize("sigma", [0.5, -0.5])
def test_f32_update_of_a_centred_gram_stays_orthogonal(sigma):
    """The mean-adjustment step of Algorithm 2: a near-constant vector on
    a centred RBF gram, whose spectrum is a dense cluster near zero.  In
    f32 the update keeps eigenvalues and orthogonality at working
    accuracy (it lost both when roots collapsed onto their poles)."""
    rng = np.random.default_rng(5)
    W = 128
    x = rng.normal(size=(W, 10))
    d2 = np.sum((x[:, None] - x[None]) ** 2, axis=-1)
    K = np.exp(-d2 / 10.0)
    r = K.mean(axis=1)
    Kc = K - r[:, None] - r[None, :] + r.mean()
    lam, U = np.linalg.eigh(Kc)
    v = 1.0 + 0.01 * rng.normal(size=W)
    want = np.linalg.eigvalsh(Kc + sigma * np.outer(v, v))
    L = rankone.sentinelize(jnp.asarray(lam, jnp.float32), jnp.int32(W),
                            jnp.float32(0.0))
    L2, U2 = rankone.rank_one_update(
        L, jnp.asarray(U, jnp.float32), jnp.asarray(v, jnp.float32),
        jnp.float32(sigma), jnp.int32(W), iters=32, precise=False)
    got = np.sort(np.asarray(L2, np.float64))
    U2 = np.asarray(U2, np.float64)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() < 1e-5 * scale
    assert np.abs(U2.T @ U2 - np.eye(W)).max() < 1e-5


@pytest.mark.parametrize("fn", ["_gu_zhat", "_exp", "_log"])
def test_gu_eisenstat_uses_exact_ops_only(fn):
    """The Gu–Eisenstat z-recompute sums M log-ratios and exponentiates
    the sum, so every step must be accurate to f32 on every backend.  A
    TPU's f32 ``exp`` and ``log`` are off by up to ~100 and ~4000 ulps;
    neither may appear in the traced computation."""
    M = 16
    d = jnp.arange(M, dtype=jnp.float32)
    args = {"_gu_zhat": (d, d, d * 0.0 + 0.5, jnp.float32(1.0), d + 1.0),
            "_exp": (d,), "_log": (d + 1.0,)}[fn]
    jaxpr = str(jax.make_jaxpr(getattr(rankone, fn))(*args))
    for prim in ("exp", "log", "exp2", "log1p", "expm1"):
        assert f" {prim}[" not in jaxpr and f" {prim} " not in jaxpr, prim
