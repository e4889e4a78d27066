"""Cholesky family tests (reference test/test_posv.cc style residual
checks: ||b - A x|| / (||A|| ||x|| n eps))."""

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import TiledMatrix, Uplo
from slate_tpu.linalg.blocked import CHOL_SCAN_STAGES


def spd(rng, n, complex_=False):
    a = rng.standard_normal((n, n))
    if complex_:
        a = a + 1j * rng.standard_normal((n, n))
    return a @ a.conj().T + n * np.eye(n)


def test_potrf_lower(rng):
    n = 50
    a = spd(rng, n)
    A = st.HermitianMatrix(Uplo.Lower, a, mb=16)
    L = st.potrf(A)
    Lnp = L.to_numpy()
    assert np.allclose(np.triu(Lnp, 1), 0)
    np.testing.assert_allclose(Lnp @ Lnp.T, a, rtol=1e-10)
    # matches scipy/numpy
    np.testing.assert_allclose(Lnp, np.linalg.cholesky(a), rtol=1e-8)


def test_potrf_upper(rng):
    n = 40
    a = spd(rng, n)
    A = st.HermitianMatrix(Uplo.Upper, a, mb=16)
    U = st.potrf(A)
    Unp = U.to_numpy()
    assert np.allclose(np.tril(Unp, -1), 0)
    np.testing.assert_allclose(Unp.T @ Unp, a, rtol=1e-10)


def test_potrf_complex(rng):
    n = 36
    a = spd(rng, n, complex_=True)
    A = st.HermitianMatrix(Uplo.Lower, a, mb=16)
    L = st.potrf(A).to_numpy()
    np.testing.assert_allclose(L @ L.conj().T, a, rtol=1e-10)


def test_posv(rng):
    n, nrhs = 60, 7
    a = spd(rng, n)
    b = rng.standard_normal((n, nrhs))
    A = st.HermitianMatrix(Uplo.Lower, a, mb=16)
    B = TiledMatrix.from_dense(b, 16)
    L, X = st.posv(A, B)
    x = X.to_numpy()
    resid = np.linalg.norm(b - a @ x) / (
        np.linalg.norm(a) * np.linalg.norm(x) * n * np.finfo(np.float64).eps)
    assert resid < 10


def test_posv_upper(rng):
    n = 30
    a = spd(rng, n)
    b = rng.standard_normal((n, 3))
    A = st.HermitianMatrix(Uplo.Upper, a, mb=8)
    _, X = st.posv(A, TiledMatrix.from_dense(b, 8))
    np.testing.assert_allclose(a @ X.to_numpy(), b, rtol=1e-8)


def test_trtri(rng):
    n = 40
    a = np.tril(rng.standard_normal((n, n))) + 3 * np.eye(n)
    T = st.TriangularMatrix(Uplo.Lower, a, mb=16)
    Ti = st.trtri(T).to_numpy()
    np.testing.assert_allclose(Ti @ np.tril(a), np.eye(n), atol=1e-9)


def test_potri(rng):
    n = 32
    a = spd(rng, n)
    A = st.HermitianMatrix(Uplo.Lower, a, mb=16)
    L = st.potrf(A)
    Ainv = st.potri(L)
    np.testing.assert_allclose(Ainv.to_numpy() @ a, np.eye(n), atol=1e-8)


def test_pbsv(rng):
    n, kd = 40, 3
    a = spd(rng, n)
    band = np.triu(np.tril(a, kd), -kd)
    band = band + n * np.eye(n)   # keep SPD after banding
    A = st.HermitianBandMatrix(Uplo.Lower, kd, band, mb=8)
    b = rng.standard_normal((n, 2))
    L, X = st.pbsv(A, TiledMatrix.from_dense(b, 8))
    full = A.to_numpy()
    np.testing.assert_allclose(full @ X.to_numpy(), b, rtol=1e-8)
    # factor stays banded
    Lnp = L.to_numpy()
    assert np.allclose(np.tril(Lnp, -(kd + 1)), 0, atol=1e-10)


def test_potrf_jit_and_ragged(rng):
    import jax
    n = 45   # not a multiple of nb
    a = spd(rng, n)
    A = st.HermitianMatrix(Uplo.Lower, a, mb=16)
    L = jax.jit(st.potrf)(A)
    Lnp = L.to_numpy()
    np.testing.assert_allclose(Lnp @ Lnp.T, a, rtol=1e-9)


def test_potrf_tiled_matches_fused(rng):
    # Tiled (blocked SPMD path) vs Fused (XLA native) numerically; n/nb
    # chosen so diagonal blocks straddle the trailing-update block
    # boundaries (regression: a symmetrize_input=True fallback averaged
    # stale upper-triangle content into diag blocks, rel err ~5e-3)
    from slate_tpu.core.methods import MethodFactor
    from slate_tpu.core.options import Option
    n = 1280
    a = spd(rng, n)
    A = st.HermitianMatrix(Uplo.Lower, a, mb=256)
    Lt = st.potrf(A, {Option.MethodFactor: MethodFactor.Tiled}).to_numpy()
    Lf = st.potrf(A, {Option.MethodFactor: MethodFactor.Fused}).to_numpy()
    np.testing.assert_allclose(Lt @ Lt.T, a, rtol=1e-9, atol=1e-10)
    np.testing.assert_allclose(Lf @ Lf.T, a, rtol=1e-9, atol=1e-10)


@pytest.mark.parametrize("n,nb,stages", [
    (16, 16, 1), (32, 16, 2), (192, 16, CHOL_SCAN_STAGES),
    (176, 16, CHOL_SCAN_STAGES)])
def test_cholesky_scan_matches_blocked(rng, n, nb, stages):
    """The fori_loop Cholesky (compile-time-safe form for huge nt), in
    one stage, in two and in as many as it takes at most (even ones,
    and 11 blocks dealt 2, 3, 3, 3), must match the unrolled blocked
    loop numerically."""
    import jax.numpy as jnp
    from slate_tpu.linalg.blocked import (chol_scan_stages, cholesky_blocked,
                                          cholesky_scan)
    assert len(chol_scan_stages(n, nb)) == stages
    a = spd(rng, n)
    aj = jnp.asarray(a)
    Ls = np.tril(np.asarray(cholesky_scan(aj, nb)))
    np.testing.assert_allclose(Ls @ Ls.T, a, rtol=1e-10, atol=1e-10)
    Lb = np.tril(np.asarray(cholesky_blocked(aj, nb)))
    np.testing.assert_allclose(Ls, Lb, rtol=1e-9, atol=1e-10)


@pytest.mark.parametrize("p,q", [(2, 2), (1, 4), (4, 1)])
def test_scan_stage_plan(p, q):
    """The stages of the scan form, from n, nb and the grid alone: they
    cover the order exactly, every boundary is a multiple of
    nb * lcm(p, q), and every trailing square keeps its blocks on one
    chip along both mesh axes."""
    import math
    import types
    from slate_tpu.linalg.blocked import (block_on_one_chip,
                                          chol_scan_stages,
                                          chol_scan_update_flops)
    grid = types.SimpleNamespace(p=p, q=q)
    for n, nb in ((49152, 512), (768, 8), (65536, 512), (1056, 8)):
        plan = chol_scan_stages(n, nb, grid)
        unit = nb * math.lcm(p, q)
        assert len(plan) == min(CHOL_SCAN_STAGES, n // unit)
        assert plan[0][0] == 0 and sum(w for _, w in plan) == n
        for (r, w), (r1, _) in zip(plan, plan[1:] + ((n, 0),)):
            assert r + w == r1 and w > 0 and r % unit == 0
            assert all(block_on_one_chip(n - r, nb, parts)
                       for parts in (p, q))
        # as even as whole units go
        widths = {w // unit for _, w in plan}
        assert max(widths) - min(widths) <= 1
        flops = chol_scan_update_flops(n, nb, grid)
        assert flops == sum(2 * (n - r) ** 2 * w for r, w in plan)
        assert 2 * n ** 3 / 3 < flops < 2 * n ** 3
    # no boundary keeps a block of 8 on one of two chips at 388 rows
    # a chip, and a single unit has nothing to cut: one stage, the
    # whole matrix at every step
    for n, nb in ((776, 8), (8 * math.lcm(p, q), 8)):
        assert chol_scan_stages(n, nb, grid) == ((0, n),)
        assert chol_scan_update_flops(n, nb, grid) == 2 * n ** 3


def test_cholesky_scan_threshold_route(rng, monkeypatch):
    # above the threshold the Tiled potrf takes the scan form and the
    # compiled program stays small regardless of nt
    import jax
    from slate_tpu.linalg import blocked
    monkeypatch.setattr(blocked, "CHOL_SCAN_THRESHOLD", 4)
    n = 128
    a = spd(rng, n)
    A = st.HermitianMatrix(Uplo.Lower, a, mb=8)   # nt = 16 > 4
    from slate_tpu.core.methods import MethodFactor
    from slate_tpu.core.options import Option
    L = st.potrf(A, {Option.MethodFactor: MethodFactor.Tiled})
    Lnp = L.to_numpy()
    np.testing.assert_allclose(Lnp @ Lnp.T, a, rtol=1e-9, atol=1e-10)


def test_potrf_lookahead_pipelined_matches_plain(rng):
    """Option.Lookahead=1 (default) takes the software-pipelined loop
    (reference potrf.cc:136-176 lookahead columns); it must agree with
    the plain right-looking order to roundoff."""
    from slate_tpu.core.methods import MethodFactor
    from slate_tpu.core.options import Option

    n, nb = 160, 16
    b = rng.standard_normal((n, n))
    a = b @ b.T / n + 4 * np.eye(n)
    A = st.HermitianMatrix(st.Uplo.Lower, a, mb=nb)
    base = {Option.MethodFactor: MethodFactor.Tiled}
    L0 = st.potrf(A, {**base, Option.Lookahead: 0})
    L1 = st.potrf(A, {**base, Option.Lookahead: 1})
    l0 = np.tril(L0.to_numpy())
    l1 = np.tril(L1.to_numpy())
    np.testing.assert_allclose(l1, l0, rtol=1e-12, atol=1e-13)
    np.testing.assert_allclose(l1 @ l1.T, a, rtol=1e-10, atol=1e-10)
