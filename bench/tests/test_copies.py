"""The benchmark's copies of the program's data stand-in and of
``chip_smoke.py``'s reference, comparison and compile clock give the
same numbers as the originals on a small CPU case."""
import importlib.util

import numpy as np
import pytest

from conftest import ROOT

from harness import check, clock, data


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("seed", [0, 3, 2**33 + 5])
def test_magic_like_is_the_programs(seed):
    from repro.data.uci_like import load_dataset

    np.testing.assert_array_equal(data.magic_like(19020, 10, seed),
                                  load_dataset("magic", seed=seed))


@pytest.fixture(scope="module")
def case(smoke):
    """A tiny windowed stream, its queries, and chip_smoke's reference."""
    import jax.numpy as jnp

    from repro.core import engine as eng, inkpca, kernels_fn as kf

    x = data.magic_like(400, 10, 11)
    W, steps, k = 48, 6, 5
    spec = kf.KernelSpec(name="rbf", sigma=10.0)
    stream = inkpca.KPCAStream(jnp.asarray(x[:W], jnp.float32), 64, spec,
                               adjusted=True,
                               plan=eng.UpdatePlan(window=W),
                               dtype=jnp.float32)
    stream.update_block(jnp.asarray(x[W:W + steps], jnp.float32))
    xq = x[300:332]
    y = stream.transform(jnp.asarray(xq, jnp.float32), n_components=k)
    ref = smoke.window_reference(x[steps:W + steps], xq, 10.0, k)
    return {"x": x, "W": W, "steps": steps, "k": k, "xq": xq, "y": y,
            "state": stream.kpca_state, "ref": ref}


def test_reference_is_chip_smokes(case):
    mod = check.load_reference("kpca_window")
    x, W, s, k = case["x"], case["W"], case["steps"], case["k"]
    mine = mod.window_reference(x[s:W + s], [case["xq"]], 10.0, k)
    ref = case["ref"]
    np.testing.assert_allclose(mine["lam"], ref["lam"], rtol=1e-10)
    np.testing.assert_allclose(mine["G"][0], ref["G"], rtol=1e-8,
                               atol=1e-10 * np.abs(ref["G"]).max())
    # the same top-k subspace: its projectors agree
    np.testing.assert_allclose(mine["U"] @ mine["U"].T, ref["U"] @ ref["U"].T,
                               atol=1e-9)


def test_comparison_is_chip_smokes(smoke, case):
    st, k, ref = case["state"], case["k"], case["ref"]
    want = smoke.compare_window(st, case["y"], ref, k)
    lam = check.top_eigenvalues(np.asarray(st.L), int(st.m), k)
    eig = float(np.max(np.abs(lam - ref["lam"][:k]) / ref["lam"][:k]))
    assert eig == pytest.approx(want["eig_rel_err"], rel=1e-12)
    # chip_smoke weighs the served rows by the state's eigenvalues; the
    # benchmark by the reference's (a snapshot carries no eigenvalues).
    y = np.asarray(case["y"])
    assert check.query_error(y, lam, ref["G"]) == pytest.approx(
        want["query_err"], rel=1e-9)
    assert check.query_error(y, ref["lam"][:k], ref["G"]) < 1e-4


def test_compile_clock_is_chip_smokes(smoke):
    import jax
    import jax.numpy as jnp

    a, b = clock.CompileClock(), smoke.CompileClock()
    a0, b0 = a.seconds, b.seconds
    jax.jit(lambda v: jnp.sin(v) * 3.0 + 1.0)(jnp.arange(7.0)).block_until_ready()
    assert a.seconds - a0 == pytest.approx(b.seconds - b0)
    assert a.seconds > a0 and a.compiles >= 1 and a.traces >= 1
