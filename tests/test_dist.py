"""dist/ subsystem tests on the 8-device CPU mesh: the explicit
ppermute combine tree, mesh TSQR (with the tree schedule asserted in
the compiled HLO, like the SUMMA test), distributed stedc vs the
single-device driver, and the row-local steqr2 accumulation
(reference ttqrt/stedc/dsteqr2 roles — ISSUE 2)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import TiledMatrix, dist
from slate_tpu.core.methods import MethodEig, MethodFactor, MethodGels
from slate_tpu.core.options import Option


def dist_opts(grid):
    return {Option.Grid: grid, Option.MethodFactor: MethodFactor.Tiled}


def shard(grid, A):
    return dataclasses.replace(
        A, data=jax.device_put(A.data, grid.matrix_sharding()))


# -- tree engine ----------------------------------------------------------

def test_tree_allreduce_matches_psum(rng, grid8):
    """The explicit ppermute butterfly must reduce like a psum, at
    every fan-in (2 = binary ttqrt tree; 4 and 8 = grouped combines)."""
    from slate_tpu.parallel import collectives as coll
    x = jnp.asarray(rng.standard_normal((16, 4)))
    xs = jax.device_put(x, grid8.row_sharding())
    ref = np.asarray(x).reshape(8, 2, 4).sum(axis=0)
    for fanin in (2, 4, 8):
        out = coll.tree_allreduce(grid8, xs, fanin=fanin)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-12)


def test_tree_round_schedule():
    from slate_tpu.dist.tree import round_schedule
    assert round_schedule(8, 2) == [(1, 2), (2, 2), (4, 2)]
    assert round_schedule(8, 4) == [(1, 4), (4, 2)]
    assert round_schedule(8, 8) == [(1, 8)]
    assert round_schedule(1, 2) == []
    # non-power-of-two sizes pick dividing group sizes
    assert round_schedule(6, 2) == [(1, 2), (2, 3)]


def test_row_apply_local(rng, grid8):
    """row_apply: sharded rows, replicated operand, no communication —
    result equals the plain product."""
    x = jnp.asarray(rng.standard_normal((24, 16)))
    g = jnp.asarray(rng.standard_normal((16, 16)))
    out = dist.row_apply(grid8, lambda xs, gg: xs @ gg, x, g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ g),
                               rtol=1e-12)


# -- mesh TSQR ------------------------------------------------------------

@pytest.mark.parametrize("fanin", [2, 4])
def test_tsqr_mesh(rng, grid8, fanin, monkeypatch):
    """Mesh TSQR: Q orthonormal, R upper triangular, Q R = A — at the
    binary and grouped fan-ins (the tree-shape tunable)."""
    from slate_tpu.tune import cache as tcache
    monkeypatch.setitem(tcache.FROZEN, ("tsqr", "tree_fanin"), fanin)
    m, w = 96, 8
    a = rng.standard_normal((m, w))
    Q, R = dist.tsqr_mesh(grid8, jnp.asarray(a))
    Qn, Rn = np.asarray(Q), np.asarray(R)
    np.testing.assert_allclose(Qn @ Rn, a, atol=1e-12)
    np.testing.assert_allclose(Qn.T @ Qn, np.eye(w), atol=1e-12)
    assert np.abs(np.tril(Rn, -1)).max() == 0


def test_tsqr_qt_solves_lstsq(rng, grid8):
    """tsqr_qt (R + Q^H B riding the same tree exchanges) must give
    the least-squares solution through one triangular solve."""
    m, w = 104, 8      # ragged: 104 = 8*13, tests the row padding
    a = rng.standard_normal((m, w))
    b = rng.standard_normal((m, 3))
    R, qtb = dist.tsqr_qt(grid8, jnp.asarray(a), jnp.asarray(b))
    x = np.linalg.solve(np.asarray(R), np.asarray(qtb))
    x_ref = np.linalg.lstsq(a, b, rcond=None)[0]
    np.testing.assert_allclose(x, x_ref, atol=1e-10)


def test_gels_tsqr_mesh_matches_single_device(rng, grid8):
    """gels_tsqr on the 2x4 mesh == single-device, with the pairwise
    tree schedule visible in the compiled HLO (collective-permute is
    ppermute's compiled signature — the evidence the explicit tree,
    not the SPMD partitioner, moved the R factors; like the SUMMA
    all-reduce assertion)."""
    m, n = 96, 8
    a = rng.standard_normal((m, n))
    b = rng.standard_normal((m, 2))
    A1 = TiledMatrix.from_dense(a, 8)
    B1 = TiledMatrix.from_dense(b, 8)
    X_ref = st.gels_tsqr(A1, B1)

    @jax.jit
    def step(A, B):
        return st.gels_tsqr(A, B, dist_opts(grid8)).data

    As, Bs = shard(grid8, A1), shard(grid8, B1)
    out = np.asarray(step(As, Bs))
    np.testing.assert_allclose(out[:n, :2],
                               np.asarray(X_ref.to_dense())[:n, :2],
                               rtol=1e-9, atol=1e-11)
    hlo = jax.jit(step).lower(As, Bs).compile().as_text()
    assert "collective-permute" in hlo


def test_gels_auto_routes_tsqr_on_grid(rng, grid8):
    """gels Auto on a grid routes tall-skinny to the TSQR tree
    (MethodGels.select on_grid) and still matches lstsq."""
    assert MethodGels.select(96, 8, on_grid=True) is MethodGels.TSQR
    assert MethodGels.select(96, 8, gram_cond=1.5) is MethodGels.CholQR
    assert MethodGels.select(96, 8) is MethodGels.QR
    assert MethodGels.select(96, 48, on_grid=True) is MethodGels.QR
    m, n = 96, 8
    a = rng.standard_normal((m, n))
    b = rng.standard_normal((m, 2))
    X_ref = np.linalg.lstsq(a, b, rcond=None)[0]

    @jax.jit
    def step(A, B):
        return st.gels(A, B, dist_opts(grid8)).data

    out = np.asarray(step(shard(grid8, TiledMatrix.from_dense(a, 8)),
                          shard(grid8, TiledMatrix.from_dense(b, 8))))
    np.testing.assert_allclose(out[:n, :2], X_ref, rtol=1e-8,
                               atol=1e-10)


def test_geqrf_grid_tall_skinny_takes_tree(rng, grid8):
    """The grid geqrf panel route: tall-skinny factors via the mesh
    tree (explicit thin Q — no replicated packed panel), and the
    packed R slot plus unmqr's isometry apply keep gels_qr exact."""
    m, n = 96, 8
    a = rng.standard_normal((m, n))
    A1 = TiledMatrix.from_dense(a, 8)
    F = st.geqrf(shard(grid8, A1), dist_opts(grid8))
    assert F.Q is not None, "grid tall-skinny geqrf did not take TSQR"
    Qn = np.asarray(F.Q.to_dense())[:m]
    Rn = np.triu(np.asarray(F.QR.to_dense())[:n, :n])
    np.testing.assert_allclose(Qn @ Rn, a, atol=1e-12)
    np.testing.assert_allclose(Qn.T @ Qn, np.eye(n), atol=1e-12)
    # thin-Q unmqr isometry: rows past n are exact zeros
    b = rng.standard_normal((m, 2))
    QtB = st.unmqr(st.Side.Left, F,
                   shard(grid8, TiledMatrix.from_dense(b, 8)),
                   trans=True, opts=dist_opts(grid8))
    qtb = np.asarray(QtB.to_dense())
    np.testing.assert_allclose(qtb[:n], Qn.T @ b, atol=1e-12)
    assert np.abs(qtb[n:]).max() == 0
    # square shapes must NOT take the tree (packed contract intact)
    sq = st.geqrf(shard(grid8, TiledMatrix.from_dense(
        rng.standard_normal((64, 64)), 8)), dist_opts(grid8))
    assert sq.Q is None


# -- distributed stedc ----------------------------------------------------

def test_stedc_dist_matches_single_device(rng, grid8):
    """8-device mesh stedc == single-device stedc (ISSUE 2 acceptance):
    the rank-parallel levels are bit-identical, the matmul-sharded top
    levels match to reduction-order rounding."""
    for n, leaf in ((100, 16), (129, 16)):
        d = rng.standard_normal(n)
        e = rng.standard_normal(n - 1)
        w1, v1 = st.stedc_solve(d, e, leaf=leaf)

        @jax.jit
        def step(dd, ee, leaf=leaf):
            return dist.stedc_solve_dist(grid8, dd, ee, leaf=leaf)

        w2, v2 = step(jnp.asarray(d), jnp.asarray(e))
        np.testing.assert_allclose(np.asarray(w2), np.asarray(w1),
                                   rtol=1e-12, atol=1e-13)
        # eigenvector sign freedom: compare residual + orthogonality
        t = np.diag(d) + np.diag(e, -1) + np.diag(e, 1)
        v2n = np.asarray(v2)
        w2n = np.asarray(w2)
        assert np.abs(t @ v2n - v2n * w2n[None, :]).max() < 1e-9
        assert np.abs(v2n.T @ v2n - np.eye(n)).max() < 1e-9


def test_heev_dc_on_mesh(rng, grid8):
    """heev MethodEig.DC end-to-end on the mesh (he2hb -> hb2st ->
    distributed stedc -> shard_map back-transform) matches numpy —
    the ISSUE 2 wiring evidence for the eig driver."""
    n = 64
    a = rng.standard_normal((n, n))
    a = (a + a.T) / 2
    A1 = st.HermitianMatrix(st.Uplo.Lower, a, mb=8)
    opts = dict(dist_opts(grid8))
    opts[Option.MethodEig] = MethodEig.DC

    @jax.jit
    def step(A):
        w, V = st.heev(A, opts)
        return w, V.data

    w, V = step(shard(grid8, A1))
    wn = np.linalg.eigvalsh(a)
    np.testing.assert_allclose(np.sort(np.asarray(w)), wn, rtol=1e-9,
                               atol=1e-10)
    v = np.asarray(V)[:n, :n]
    ws = np.asarray(w)
    assert np.abs(a @ v - v * ws[None, :]).max() < 1e-8
    assert np.abs(v.T @ v - np.eye(n)).max() < 1e-8


# -- row-local steqr2 -----------------------------------------------------

def test_steqr2_dist_bitwise_matches_single(rng, grid8):
    """The row-local shard_map accumulation is communication-free per
    sweep, so the mesh result must be BIT-IDENTICAL to single-device
    steqr2_qr — every device runs the same recurrence and multiplies
    the same composed chain."""
    from slate_tpu.linalg.eig import steqr2_qr
    n = 64
    d = rng.standard_normal(n)
    e = rng.standard_normal(n - 1)
    w1, Z1, i1 = steqr2_qr(jnp.asarray(d), jnp.asarray(e))
    w2, Z2, i2 = dist.steqr2_qr_dist(grid8, jnp.asarray(d),
                                     jnp.asarray(e))
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
    np.testing.assert_array_equal(np.asarray(Z1), np.asarray(Z2))
    assert int(i1) == int(i2) == 0


def test_steqr2_driver_on_mesh_applies_q(rng, grid8):
    """The steqr2 driver under Option.Grid: Q rides the row-local
    accumulation directly (the dsteqr2.f slot) and the result matches
    the dense eigendecomposition."""
    n = 48
    d = rng.standard_normal(n)
    e = rng.standard_normal(n - 1)
    q0 = np.linalg.qr(rng.standard_normal((n, n)))[0]
    Q = TiledMatrix.from_dense(q0, 8)

    @jax.jit
    def step(dd, ee, Qd):
        w, V = st.steqr2(dd, ee, dataclasses.replace(Q, data=Qd),
                         dist_opts(grid8))
        return w, V.data

    w, V = step(jnp.asarray(d), jnp.asarray(e), Q.data)
    T = np.diag(d) + np.diag(e, 1) + np.diag(e, -1)
    np.testing.assert_allclose(np.asarray(w), np.linalg.eigvalsh(T),
                               rtol=1e-10, atol=1e-12)
    # V = Q0 Z, so Q0^T V diagonalizes T
    Z = q0.T @ np.asarray(V)[:n, :n]
    np.testing.assert_allclose(Z @ np.diag(np.asarray(w)) @ Z.T, T,
                               atol=1e-10)


def test_steqr2_separated_spectrum_medium(rng):
    """steqr2 well above the old 512 cap (no reroute — stedc is NOT
    called), against scipy. A separated spectrum with weak coupling
    keeps the sweep count low; the ISSUE 2 target size of 4096 is a
    TPU-scale run (the composed-chain accumulation is ~n^3 flops per
    sweep, hours on the 1-core CI box — measured 106 s already at
    n=1024), so CI pins the contract at 1024."""
    import scipy.linalg as sla
    n = 1024
    d = np.arange(n) + 0.3 * rng.standard_normal(n)
    e = 1e-3 * rng.standard_normal(n - 1)
    w, Z = st.steqr2(np.asarray(d), np.asarray(e))
    w = np.asarray(w)
    ws = sla.eigh_tridiagonal(d, e, eigvals_only=True)
    np.testing.assert_allclose(w, ws, rtol=1e-9, atol=1e-9)
    # sampled residual (full n^3 check would dominate the test)
    T = np.diag(d) + np.diag(e, 1) + np.diag(e, -1)
    Zn = np.asarray(Z)
    cols = rng.choice(n, 16, replace=False)
    assert np.abs(T @ Zn[:, cols]
                  - Zn[:, cols] * w[cols][None, :]).max() < 1e-8
