"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp ref."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.eigvec_update.eigvec_update import eigvec_rotate
from repro.kernels.eigvec_update.ref import eigvec_rotate_ref
from repro.kernels.nystrom_recon.nystrom_recon import scaled_gram
from repro.kernels.nystrom_recon.ref import scaled_gram_ref
from repro.kernels.rbf_gram.rbf_gram import rbf_gram
from repro.kernels.rbf_gram.ref import rbf_gram_ref

RNG = np.random.default_rng(3)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("M", [32, 128, 200, 257])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_eigvec_rotate_sweep(M, dtype):
    u = jnp.asarray(RNG.normal(size=(M, M)), dtype)
    z = jnp.asarray(RNG.normal(size=M), dtype)
    d = jnp.asarray(np.sort(RNG.normal(size=M)), dtype)
    lam = d + 0.4
    inv = jnp.asarray(RNG.uniform(0.5, 2.0, size=M), dtype)
    out = eigvec_rotate(u, z, d, lam, inv, interpret=True, block=128)
    ref = eigvec_rotate_ref(u, z, d, lam, inv)
    np.testing.assert_allclose(np.asarray(out, np.float64),
                               np.asarray(ref, np.float64),
                               rtol=5e-3, atol=5e-3)
    assert np.isfinite(np.asarray(out, np.float64)).all()


def _padded_rotation_inputs(M, m, extra_shift=0.4):
    """Inputs honoring the rankone padding contract: U identity beyond the
    active block, zhat/inv zero and d/lam sentinel beyond m."""
    U = np.eye(M, dtype=np.float32)
    q, _ = np.linalg.qr(RNG.normal(size=(m, m)))
    U[:m, :m] = q
    mask = np.arange(M) < m
    z = np.where(mask, RNG.normal(size=M), 0.0)
    d = np.sort(RNG.normal(size=M))
    lam = d + extra_shift
    inv = RNG.uniform(0.5, 2.0, size=M)
    to = lambda v: jnp.asarray(v, jnp.float32)
    return (to(U), to(z), to(np.where(mask, d, 2e30)),
            to(np.where(mask, lam, 1e30)), to(np.where(mask, inv, 0.0)))


@pytest.mark.parametrize("M,m", [(200, 70), (256, 130), (300, 257)])
def test_eigvec_rotate_grid_pruning(M, m):
    """Pruned grid (num_active=m, m NOT a multiple of the block) must match
    the unpruned reference on all rows of the active columns, and return
    zeros beyond the active tile range."""
    u, z, d, lam, inv = _padded_rotation_inputs(M, m)
    block = 64
    out = eigvec_rotate(u, z, d, lam, inv, jnp.int32(m), interpret=True,
                        block=block)
    ref = eigvec_rotate_ref(u, z, d, lam, inv)
    np.testing.assert_allclose(np.asarray(out[:, :m], np.float64),
                               np.asarray(ref[:, :m], np.float64),
                               rtol=5e-3, atol=5e-3)
    g = -(-m // block)
    tiles = -(-M // block)
    if g < tiles:
        assert np.abs(np.asarray(out[:, g * block:])).max() == 0.0


def test_eigvec_rotate2_matches_two_rotations():
    """Fused double rotation == two sequential single rotations (and the
    dense ref), including deflated identity columns with a permuted cid."""
    from repro.kernels.eigvec_update.eigvec_update import eigvec_rotate2
    from repro.kernels.eigvec_update.ref import (cauchy_factor_ref,
                                                 eigvec_rotate2_ref)
    M, m, block = 200, 70, 64
    u, z1, d1, lam1, inv1 = _padded_rotation_inputs(M, m)
    _, z2, d2, lam2, inv2 = _padded_rotation_inputs(M, m, extra_shift=0.9)
    defl1 = jnp.zeros(M, jnp.float32).at[5].set(1.0)
    defl2 = jnp.zeros(M, jnp.float32).at[9].set(1.0)
    cid1 = jnp.arange(M, dtype=jnp.int32).at[5].set(12)
    cid2 = jnp.arange(M, dtype=jnp.int32)
    args = (z1, d1, lam1, inv1, defl1, cid1, z2, d2, lam2, inv2, defl2,
            cid2)

    ref = eigvec_rotate2_ref(u, *args)
    # two sequential dense rotations, spelled out
    W1 = cauchy_factor_ref(z1, d1, lam1, inv1, defl1, cid1)
    W2 = cauchy_factor_ref(z2, d2, lam2, inv2, defl2, cid2)
    np.testing.assert_allclose(np.asarray((u @ W1) @ W2), np.asarray(ref))

    for na in (None, jnp.int32(m)):
        out = eigvec_rotate2(u, *args, na, interpret=True, block=block)
        np.testing.assert_allclose(np.asarray(out[:, :m], np.float64),
                                   np.asarray(ref[:, :m], np.float64),
                                   rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.float64, 1e-12)])
@pytest.mark.parametrize("R,off", [(100, 0), (100, 100), (64, 64),
                                   (90, 30)])
def test_eigvec_rotate_rectangular_matches_ref(R, off, dtype, tol):
    """Rectangular (R, M) row blocks at any row offset must match the
    dense ref on the active columns (rel. tol 1e-5 f32 / 1e-12 f64) and
    return exact zeros on kernel-pruned rows/columns.  The ref is
    evaluated in float64 from the same inputs: these Cauchy factors have
    poles between the grid points, so an entry can be a small difference
    of terms 400x its size, and an f32 ref rounds it by more than the
    tolerance on its own."""
    from repro.kernels.eigvec_update.ref import pruned_region_mask
    M, m, block = 200, 70, 64
    u, z, d, lam, inv = (v.astype(dtype)
                         for v in _padded_rotation_inputs(M, m))
    blk = u[off:off + R]
    out = eigvec_rotate(blk, z, d, lam, inv, jnp.int32(m), jnp.int32(off),
                        interpret=True, block=block)
    ref = eigvec_rotate_ref(*(v.astype(jnp.float64)
                              for v in (u, z, d, lam, inv)))[off:off + R]
    np.testing.assert_allclose(np.asarray(out[:, :m], np.float64),
                               np.asarray(ref[:, :m]), rtol=tol, atol=tol)
    row_mask, col_mask = (np.asarray(v) for v in
                          pruned_region_mask(R, M, m, off, block=block))
    if (~col_mask).any():
        assert np.abs(np.asarray(out[:, ~col_mask])).max() == 0.0
    if (~row_mask).any():
        assert np.abs(np.asarray(out[~row_mask])).max() == 0.0


def test_eigvec_rotate_grid_is_pruned_when_m_below_capacity():
    """The scalar-prefetched tile counts must shrink below the full grid
    whenever m < M — on both axes, including offset row blocks."""
    from repro.kernels.eigvec_update.eigvec_update import _tile_counts
    M, R, m, block = 512, 128, 70, 64
    steps_r, steps_c = R // block, M // block
    g = np.asarray(_tile_counts(jnp.int32(m), jnp.int32(0), R, M, block,
                                steps_r, steps_c))
    assert g[1] == -(-m // block) < steps_c          # columns pruned
    assert g[0] == -(-m // block) == g[1]            # offset-0 rows pruned
    # block fully past the active prefix: zero row tiles survive
    g = np.asarray(_tile_counts(jnp.int32(m), jnp.int32(256), R, M, block,
                                steps_r, steps_c))
    assert g[0] == 0 and g[1] == -(-m // block)
    # no pruning info -> full grid
    g = np.asarray(_tile_counts(None, None, R, M, block, steps_r, steps_c))
    assert g[0] == steps_r and g[1] == steps_c


def test_eigvec_rotate2_rectangular_matches_ref():
    """Fused double rotation on rectangular row blocks == dense ref rows,
    including deflated identity columns and row-axis pruning."""
    from repro.kernels.eigvec_update.eigvec_update import eigvec_rotate2
    from repro.kernels.eigvec_update.ref import eigvec_rotate2_ref
    M, m, block = 200, 70, 64
    u, z1, d1, lam1, inv1 = _padded_rotation_inputs(M, m)
    _, z2, d2, lam2, inv2 = _padded_rotation_inputs(M, m, extra_shift=0.9)
    defl1 = jnp.zeros(M, jnp.float32).at[5].set(1.0)
    defl2 = jnp.zeros(M, jnp.float32).at[9].set(1.0)
    cid1 = jnp.arange(M, dtype=jnp.int32).at[5].set(12)
    cid2 = jnp.arange(M, dtype=jnp.int32)
    args = (z1, d1, lam1, inv1, defl1, cid1, z2, d2, lam2, inv2, defl2,
            cid2)
    ref = eigvec_rotate2_ref(u, *args)
    for R, off in ((100, 0), (100, 100), (90, 30)):
        out = eigvec_rotate2(u[off:off + R], *args, jnp.int32(m),
                             jnp.int32(off), interpret=True, block=block)
        scale = np.abs(np.asarray(ref[off:off + R, :m])).max() + 1.0
        np.testing.assert_allclose(
            np.asarray(out[:, :m], np.float64) / scale,
            np.asarray(ref[off:off + R, :m], np.float64) / scale,
            rtol=1e-5, atol=1e-5)


def test_rank_one_update_row_blocks_match_full_both_signs():
    """rank_one_update applied to row blocks (via the interpret-mode rect
    Pallas kernel and the un-flip) must reproduce the full update's rows
    for sigma of EITHER sign — active stays a prefix under the flip."""
    import os
    from repro.core import rankone
    rng = np.random.default_rng(11)
    m, M, R = 10, 32, 16
    A = rng.normal(size=(m, m))
    A = A @ A.T
    lam, vec = np.linalg.eigh(A)
    L0 = np.zeros(M, np.float32)
    U0 = np.eye(M, dtype=np.float32)
    L0[:m] = lam
    U0[:m, :m] = vec
    L0 = rankone.sentinelize(jnp.asarray(L0), jnp.int32(m), jnp.float32(0.0))
    v = np.zeros(M, np.float32)
    v[:m] = rng.normal(size=m)
    for sigma in (1.3, -1.3):
        Lf, Uf = rankone.rank_one_update(
            L0, jnp.asarray(U0), jnp.asarray(v), jnp.float32(sigma),
            jnp.int32(m), precise=False)
        os.environ["REPRO_PALLAS_FORCE"] = "interpret"
        try:
            for off in (0, R):
                blk = jnp.asarray(U0[off:off + R])
                z = jnp.asarray(U0.T @ v)
                Lb, Ub = rankone._update_body(
                    L0, blk, jnp.asarray(v), jnp.float32(sigma),
                    jnp.int32(m), iters=32, method="gu", matmul="pallas",
                    precise=False, z=z, row_offset=jnp.int32(off))
        finally:
            os.environ["REPRO_PALLAS_FORCE"] = "ref"
        np.testing.assert_allclose(np.asarray(Lb[:m]), np.asarray(Lf[:m]),
                                   atol=2e-5)


def test_rank_one_update_pair_matches_sequential_pallas():
    """rank_one_update_pair(matmul='pallas') through the interpret-mode
    fused kernel == two sequential jnp updates."""
    import os
    from repro.core import rankone
    m, M = 10, 16
    A = RNG.normal(size=(m, m))
    A = A @ A.T
    lam, vec = np.linalg.eigh(A)
    L = np.zeros(M)
    U = np.eye(M)
    L[:m] = lam
    U[:m, :m] = vec
    L = rankone.sentinelize(jnp.asarray(L, jnp.float32), jnp.int32(m),
                            jnp.float32(0.0))
    v1 = np.zeros(M)
    v1[:m] = RNG.normal(size=m)
    v2 = np.zeros(M)
    v2[:m] = RNG.normal(size=m)
    La, Ua = rankone.rank_one_update(
        L, jnp.asarray(U, jnp.float32), jnp.asarray(v1, jnp.float32),
        jnp.float32(1.1), jnp.int32(m), precise=False)
    La, Ua = rankone.rank_one_update(
        La, Ua, jnp.asarray(v2, jnp.float32), jnp.float32(-1.1),
        jnp.int32(m), precise=False)
    os.environ["REPRO_PALLAS_FORCE"] = "interpret"
    try:
        Lp, Up = rankone.rank_one_update_pair(
            L, jnp.asarray(U, jnp.float32), jnp.asarray(v1, jnp.float32),
            jnp.float32(1.1), jnp.asarray(v2, jnp.float32),
            jnp.float32(-1.1), jnp.int32(m), matmul="pallas", precise=False)
    finally:
        os.environ["REPRO_PALLAS_FORCE"] = "ref"
    np.testing.assert_allclose(np.asarray(Lp[:m]), np.asarray(La[:m]),
                               atol=1e-4)
    np.testing.assert_allclose(np.abs(np.asarray(Up[:m, :m])),
                               np.abs(np.asarray(Ua[:m, :m])), atol=1e-3)


@pytest.mark.parametrize("n,m,d", [(64, 64, 8), (150, 90, 17), (129, 257, 33)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rbf_gram_sweep(n, m, d, dtype):
    x = jnp.asarray(RNG.normal(size=(n, d)), dtype)
    y = jnp.asarray(RNG.normal(size=(m, d)), dtype)
    sigma = jnp.asarray(2.5, jnp.float32)
    g = rbf_gram(x, y, sigma, interpret=True)
    ref = rbf_gram_ref(x, y, sigma)
    np.testing.assert_allclose(np.asarray(g, np.float64),
                               np.asarray(ref, np.float64), **_tol(dtype))
    assert g.dtype == dtype


def test_rbf_gram_diagonal_is_one():
    x = jnp.asarray(RNG.normal(size=(40, 7)), jnp.float32)
    g = rbf_gram(x, x, jnp.asarray(3.0, jnp.float32), interpret=True)
    np.testing.assert_allclose(np.diag(np.asarray(g)), 1.0, atol=1e-5)


@pytest.mark.parametrize("n,m", [(64, 32), (170, 60), (130, 129)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_scaled_gram_sweep(n, m, dtype):
    b = jnp.asarray(RNG.normal(size=(n, m)), dtype)
    s = jnp.asarray(RNG.uniform(0.1, 1.0, size=m), dtype)
    k = scaled_gram(b, s, interpret=True)
    ref = scaled_gram_ref(b, s)
    np.testing.assert_allclose(np.asarray(k, np.float64),
                               np.asarray(ref, np.float64),
                               rtol=1e-3, atol=1e-3)
    # symmetry
    np.testing.assert_allclose(np.asarray(k), np.asarray(k).T, atol=1e-5)


@pytest.mark.parametrize("BH,T,hd", [(2, 64, 32), (3, 128, 64), (1, 64, 100)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel_sweep(BH, T, hd, dtype):
    from repro.kernels.flash_attn.flash_attn import flash_attention
    from repro.kernels.flash_attn.ref import flash_attention_ref
    q = jnp.asarray(RNG.normal(size=(BH, T, hd)) * 0.5, dtype)
    k = jnp.asarray(RNG.normal(size=(BH, T, hd)) * 0.5, dtype)
    v = jnp.asarray(RNG.normal(size=(BH, T, hd)), dtype)
    out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    ref = flash_attention_ref(q, k, v)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float64),
                               np.asarray(ref, np.float64),
                               rtol=tol, atol=tol)
    assert out.dtype == dtype


def test_flash_attention_kernel_causality():
    from repro.kernels.flash_attn.flash_attn import flash_attention
    q = jnp.asarray(RNG.normal(size=(1, 64, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 64, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 64, 32)), jnp.float32)
    o1 = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    k2 = k.at[:, -1].add(10.0)
    v2 = v.at[:, -1].add(10.0)
    o2 = flash_attention(q, k2, v2, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(o1[:, :-1]),
                               np.asarray(o2[:, :-1]), atol=1e-6)


@pytest.mark.parametrize("G,Q,N,H,P", [(2, 16, 8, 2, 16), (3, 32, 16, 4, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_ssd_chunk_kernel_sweep(G, Q, N, H, P, dtype):
    from repro.kernels.ssd_chunk.ssd_chunk import ssd_intra_chunk
    from repro.kernels.ssd_chunk.ref import ssd_intra_chunk_ref
    c = jnp.asarray(RNG.normal(size=(G, Q, N)) * 0.3, dtype)
    b = jnp.asarray(RNG.normal(size=(G, Q, N)) * 0.3, dtype)
    x = jnp.asarray(RNG.normal(size=(G, Q, H, P)), dtype)
    cum = jnp.asarray(-np.abs(np.cumsum(RNG.uniform(0, 0.2, (G, Q, H)),
                                        axis=1)), jnp.float32)
    out = ssd_intra_chunk(c, b, x, cum, interpret=True)
    ref = ssd_intra_chunk_ref(c, b, x, cum)
    np.testing.assert_allclose(np.asarray(out, np.float64),
                               np.asarray(ref, np.float64),
                               rtol=2e-4, atol=2e-4)


def test_ssd_chunk_kernel_causality():
    from repro.kernels.ssd_chunk.ssd_chunk import ssd_intra_chunk
    G, Q, N, H, P = 1, 16, 8, 2, 8
    c = jnp.asarray(RNG.normal(size=(G, Q, N)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(G, Q, N)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(G, Q, H, P)), jnp.float32)
    cum = jnp.zeros((G, Q, H), jnp.float32)
    o1 = ssd_intra_chunk(c, b, x, cum, interpret=True)
    x2 = x.at[:, -1].add(5.0)
    o2 = ssd_intra_chunk(c, b, x2, cum, interpret=True)
    np.testing.assert_allclose(np.asarray(o1[:, :-1]),
                               np.asarray(o2[:, :-1]), atol=1e-6)


def test_eigvec_rotate_used_in_rank_one_update():
    """End-to-end: rank_one_update(matmul='pallas') == 'jnp' (interpret)."""
    import os
    import jax
    from repro.core import rankone
    os.environ["REPRO_PALLAS_FORCE"] = "interpret"
    try:
        m, M = 10, 16
        A = RNG.normal(size=(m, m))
        A = A @ A.T
        lam, vec = np.linalg.eigh(A)
        L = np.zeros(M); U = np.eye(M)
        L[:m] = lam; U[:m, :m] = vec
        L = rankone.sentinelize(jnp.asarray(L, jnp.float32), jnp.int32(m),
                                jnp.float32(0.0))
        v = np.zeros(M); v[:m] = RNG.normal(size=m)
        with jax.disable_jit():
            La, Ua = rankone.rank_one_update(
                jnp.asarray(L, jnp.float32), jnp.asarray(U, jnp.float32),
                jnp.asarray(v, jnp.float32), jnp.float32(0.9), jnp.int32(m),
                matmul="pallas", precise=False)
        Lb, Ub = rankone.rank_one_update(
            jnp.asarray(L, jnp.float32), jnp.asarray(U, jnp.float32),
            jnp.asarray(v, jnp.float32), jnp.float32(0.9), jnp.int32(m),
            matmul="jnp", precise=False)
        np.testing.assert_allclose(np.asarray(La), np.asarray(Lb), atol=1e-5)
        np.testing.assert_allclose(np.abs(np.asarray(Ua[:m, :m])),
                                   np.abs(np.asarray(Ub[:m, :m])), atol=1e-3)
    finally:
        os.environ["REPRO_PALLAS_FORCE"] = "ref"


def test_rotation_kernels_take_pole_relative_roots():
    """Roots passed as (pole, offset): the kernels form the distance as
    (d - lam) - tau, exactly as the dense oracles do."""
    from repro.kernels.eigvec_update.eigvec_update import eigvec_rotate2
    from repro.kernels.eigvec_update.ref import eigvec_rotate2_ref

    M, m = 200, 150
    u, z, d, lam, inv = _padded_rotation_inputs(M, m)
    mask = np.arange(M) < m
    tau = jnp.asarray(np.where(mask, RNG.uniform(-1e-3, 1e-3, M), 0.0),
                      jnp.float32)
    out = eigvec_rotate(u, z, d, lam, inv, jnp.int32(m), None, tau,
                        interpret=True, block=64)
    ref = eigvec_rotate_ref(u, z, d, lam, inv, tau)
    # The f32 sweep tolerances of the tests above.
    np.testing.assert_allclose(np.asarray(out[:, :m]),
                               np.asarray(ref[:, :m]), rtol=5e-3, atol=5e-3)
    defl = jnp.asarray(~mask, jnp.float32)
    cid = jnp.arange(M, dtype=jnp.int32)
    args = (z, d, lam, inv, defl, cid, z, d, lam + 0.5, inv, defl, cid)
    out2 = eigvec_rotate2(u, *args, jnp.int32(m), None, tau, -tau,
                          interpret=True, block=64)
    ref2 = eigvec_rotate2_ref(u, *args, tau, -tau)
    np.testing.assert_allclose(np.asarray(out2[:, :m]),
                               np.asarray(ref2[:, :m]), rtol=5e-3, atol=5e-3)


def test_pallas_route_refuses_64_bit_operands():
    """The compiled TPU kernels are 32-bit: a float64 state on the
    ``pallas`` route raises a clear error instead of reaching the TPU
    compiler."""
    from repro.kernels.eigvec_update import ops

    M = 16
    v = jnp.ones((M,), jnp.float64)
    with pytest.raises(ValueError, match="32-bit operands"):
        ops.rotate_vectors(jnp.eye(M, dtype=jnp.float64), v, v, v, v,
                           force="pallas")
