"""QR/LQ/gels tests (reference test/test_gels.cc, test_geqrf.cc,
unit_test/test_qr.cc style checks)."""

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import Side, TiledMatrix


def M(a, nb=16):
    return TiledMatrix.from_dense(a, nb)


def reconstruct_q(F, m):
    """Apply Q to identity columns."""
    eye = np.eye(m)
    Q = st.unmqr(Side.Left, F, M(eye, F.QR.nb), trans=False)
    return Q.to_numpy()


def test_geqrf_square(rng):
    n = 48
    a = rng.standard_normal((n, n))
    F = st.geqrf(M(a))
    R = np.triu(F.QR.to_numpy())
    Q = reconstruct_q(F, n)
    np.testing.assert_allclose(Q.T @ Q, np.eye(n), atol=1e-10)
    np.testing.assert_allclose(Q @ R, a, rtol=1e-9, atol=1e-11)


def test_geqrf_tall(rng):
    m, n = 80, 24
    a = rng.standard_normal((m, n))
    F = st.geqrf(M(a))
    R = np.triu(F.QR.to_numpy())[:n]
    Q = reconstruct_q(F, m)[:, :n]
    np.testing.assert_allclose(Q.T @ Q, np.eye(n), atol=1e-10)
    np.testing.assert_allclose(Q @ R, a, rtol=1e-9, atol=1e-11)


def test_geqrf_complex(rng):
    m, n = 30, 20
    a = rng.standard_normal((m, n)) + 1j * rng.standard_normal((m, n))
    F = st.geqrf(M(a, 8))
    eye = np.eye(m, dtype=complex)
    Q = st.unmqr(Side.Left, F, M(eye, 8), trans=False).to_numpy()
    np.testing.assert_allclose(Q.conj().T @ Q, np.eye(m), atol=1e-10)
    R = np.triu(F.QR.to_numpy())
    np.testing.assert_allclose(Q[:, :n] @ R[:n], a, rtol=1e-9, atol=1e-10)


def test_geqrf_matches_numpy_r(rng):
    m, n = 40, 16
    a = rng.standard_normal((m, n))
    F = st.geqrf(M(a, 8))
    R = np.triu(F.QR.to_numpy())[:n]
    _, Rnp = np.linalg.qr(a)
    # R unique up to sign of rows
    s = np.sign(np.diagonal(R)) * np.sign(np.diagonal(Rnp))
    np.testing.assert_allclose(R, s[:, None] * Rnp, rtol=1e-8, atol=1e-10)


def test_unmqr_right(rng):
    n = 32
    a = rng.standard_normal((n, n))
    c = rng.standard_normal((10, n))
    F = st.geqrf(M(a, 8))
    Q = reconstruct_q(F, n)
    CQ = st.unmqr(Side.Right, F, M(c, 8), trans=False)
    np.testing.assert_allclose(CQ.to_numpy(), c @ Q, rtol=1e-9, atol=1e-10)
    CQh = st.unmqr(Side.Right, F, M(c, 8), trans=True)
    np.testing.assert_allclose(CQh.to_numpy(), c @ Q.T, rtol=1e-9,
                               atol=1e-10)


def test_gels_overdetermined(rng):
    m, n, nrhs = 60, 20, 3
    a = rng.standard_normal((m, n))
    b = rng.standard_normal((m, nrhs))
    X = st.gels(M(a), M(b))
    x = X.to_numpy()[:n]
    xnp, *_ = np.linalg.lstsq(a, b, rcond=None)
    np.testing.assert_allclose(x, xnp, rtol=1e-8, atol=1e-10)


def test_gels_qr_vs_cholqr(rng):
    m, n = 90, 10
    a = rng.standard_normal((m, n))
    b = rng.standard_normal((m, 2))
    x1 = st.gels_qr(M(a), M(b)).to_numpy()[:n]
    x2 = st.gels_cholqr(M(a), M(b)).to_numpy()[:n]
    np.testing.assert_allclose(x1, x2, rtol=1e-6, atol=1e-8)


def test_gels_underdetermined(rng):
    m, n = 16, 40
    a = rng.standard_normal((m, n))
    b = rng.standard_normal((m, 2))
    X = st.gels(M(a, 8), M(b, 8))
    x = X.to_numpy()[:n]
    np.testing.assert_allclose(a @ x, b, rtol=1e-8)
    xnp, *_ = np.linalg.lstsq(a, b, rcond=None)
    np.testing.assert_allclose(x, xnp, rtol=1e-7, atol=1e-9)


def test_gelqf_unmlq(rng):
    m, n = 20, 50
    a = rng.standard_normal((m, n))
    F = st.gelqf(M(a, 8))
    L = np.tril(F.LQ.to_numpy())
    eye = np.eye(n)
    Q = st.unmlq(Side.Left, F, M(eye, 8), trans=False).to_numpy()
    np.testing.assert_allclose(Q @ Q.T, np.eye(n), atol=1e-10)
    np.testing.assert_allclose(L[:, :m] @ Q[:m], a, rtol=1e-8, atol=1e-10)


def test_cholqr(rng):
    m, n = 70, 12
    a = rng.standard_normal((m, n))
    Q, R = st.cholqr(M(a, 8))
    q = Q.to_numpy()
    np.testing.assert_allclose(q.T @ q, np.eye(n), atol=1e-8)
    np.testing.assert_allclose(q @ R.to_numpy()[:n, :n], a, rtol=1e-8)


def test_geqrf_jit(rng):
    import jax
    a = rng.standard_normal((32, 32))
    F = jax.jit(st.geqrf)(M(a, 8))
    assert np.isfinite(F.QR.to_numpy()).all()


def test_geqrf_scan_matches_unrolled(rng, monkeypatch):
    """Fixed-shape fori_loop geqrf (compile-safe huge-nt form) must
    reproduce the unrolled blocked factorization."""
    from slate_tpu.linalg import qr as qrmod
    n, nb = 96, 8
    a = rng.standard_normal((n, n))
    F_ref = st.geqrf(M(a, nb))
    monkeypatch.setattr(qrmod, "QR_SCAN_THRESHOLD", 4)
    F_s = st.geqrf(M(a, nb))
    np.testing.assert_allclose(np.asarray(F_s.taus),
                               np.asarray(F_ref.taus), rtol=1e-12,
                               atol=1e-13)
    np.testing.assert_allclose(F_s.QR.to_numpy(), F_ref.QR.to_numpy(),
                               rtol=1e-11, atol=1e-12)
    # solve through the scan factors end to end
    b = rng.standard_normal((n, 2))
    X = st.gels(M(a, nb), M(b, nb))
    np.testing.assert_allclose(X.to_numpy()[:n, :2],
                               np.linalg.lstsq(a, b, rcond=None)[0],
                               rtol=1e-8, atol=1e-9)


def test_unmqr_scan_matches_unrolled(rng, monkeypatch):
    """Fixed-shape fori_loop unmqr (all four side/trans cases) must
    reproduce the unrolled apply — this closes the huge-n chain for
    gels and the heev/svd back-transforms (round-2 gap: unmqr unrolled
    O(nt) Python loops one call after the factorizations went O(1))."""
    from slate_tpu.core.enums import Side
    from slate_tpu.linalg import qr as qrmod

    qr_threshold_default = qrmod.QR_SCAN_THRESHOLD
    # n=100 is deliberately ragged (kmax=100 < padded 104): regression
    # for the tpad scatter crash when taus carries the padded length
    for n, nb in ((96, 8), (100, 8)):
        a = rng.standard_normal((n, n))
        F = st.geqrf(M(a, nb))
        c = rng.standard_normal((n, n))

        refs = {}
        for side in (Side.Left, Side.Right):
            for trans in (False, True):
                refs[(side, trans)] = st.unmqr(
                    side, F, M(c, nb), trans=trans).to_numpy()

        monkeypatch.setattr(qrmod, "QR_SCAN_THRESHOLD", 4)
        for (side, trans), ref in refs.items():
            got = st.unmqr(side, F, M(c, nb), trans=trans).to_numpy()
            np.testing.assert_allclose(got, ref, rtol=1e-11, atol=1e-12,
                                       err_msg=f"{side} trans={trans}")
        monkeypatch.setattr(qrmod, "QR_SCAN_THRESHOLD",
                            qr_threshold_default)

    # end-to-end: gels entirely through scan forms (geqrf + unmqr)
    monkeypatch.setattr(qrmod, "QR_SCAN_THRESHOLD", 4)
    b = rng.standard_normal((n, 2))
    X = st.gels(M(a, nb), M(b, nb))
    np.testing.assert_allclose(X.to_numpy()[:n, :2],
                               np.linalg.lstsq(a, b, rcond=None)[0],
                               rtol=1e-8, atol=1e-9)


@pytest.mark.parametrize("shape", [(64, 48), (72, 44)],
                         ids=["whole_panels", "ragged_last_panel"])
@pytest.mark.parametrize("dtype", [np.float64, np.complex128],
                         ids=["real", "complex"])
@pytest.mark.parametrize("trans", [True, False], ids=["QH", "Q"])
@pytest.mark.parametrize("side", [Side.Left, Side.Right],
                         ids=["left", "right"])
def test_unmqr_compiled_apply_matches_explicit_q(rng, side, trans, dtype,
                                                 shape):
    """The packed-factor apply is ONE compiled program (qr._unmqr_apply:
    the pad of C, the unrolled loop over the shrinking panels, the
    crop). Every side and op, real and complex, with kmax a multiple
    of nb (48 = 3 x 16) and not (44: a last panel 12 wide), gives
    what the explicit Q gives: Q from the same apply on the identity,
    itself held to Q R = A and Q^H Q = I. The tolerance is
    test_unmqr_scan_matches_unrolled's. A caller already under a
    trace gets the same numbers (the program inlines)."""
    import jax

    from slate_tpu.linalg import qr as qrmod
    m, n = shape
    nb, r = 16, 5

    def draw(*dims):
        x = rng.standard_normal(dims)
        if dtype is np.complex128:
            x = x + 1j * rng.standard_normal(dims)
        return x.astype(dtype)

    a = draw(m, n)
    F = st.geqrf(M(a, nb))
    assert qrmod._unmqr_form(F) == ("compiled", -(-n // nb))
    Q = st.unmqr(Side.Left, F, M(np.eye(m, dtype=dtype), nb),
                 trans=False).to_numpy()
    np.testing.assert_allclose(Q.conj().T @ Q, np.eye(m), atol=1e-12)
    np.testing.assert_allclose(Q[:, :n] @ np.triu(F.QR.to_numpy()[:n]), a,
                               rtol=1e-11, atol=1e-12)
    op = Q.conj().T if trans else Q
    c = draw(m, r) if side is Side.Left else draw(r, m)
    want = op @ c if side is Side.Left else c @ op
    got = st.unmqr(side, F, M(c, nb), trans=trans)
    assert got.to_numpy().shape == c.shape
    np.testing.assert_allclose(got.to_numpy(), want, rtol=1e-11, atol=1e-12)
    traced = jax.jit(lambda f, x: st.unmqr(side, f, M(x, nb),
                                           trans=trans).data)(F, c)
    np.testing.assert_allclose(np.asarray(traced), np.asarray(got.data),
                               rtol=1e-11, atol=1e-12)


def test_geqrf_fused_packed(rng):
    """MethodFactor.Fused geqrf = one whole-matrix native geqrf with
    the PACKED Householder contract (the explicit-Q form was retired:
    quadratic-in-rows memory and measured slower, PERF.md); unmqr and
    gels consume it like any packed factor."""
    from slate_tpu.core.methods import MethodFactor
    from slate_tpu.core.options import Option
    from slate_tpu.core.enums import Side

    m, n = 48, 32
    a = rng.standard_normal((m, n))
    opts = {Option.MethodFactor: MethodFactor.Fused}
    F = st.geqrf(M(a, 8), opts)
    assert F.Q is None
    # packed semantics: Q from the Householder vectors reproduces A
    Fd = st.geqrf(M(a, 8))           # default path, same contract
    np.testing.assert_allclose(np.triu(F.QR.to_numpy())[:n, :n],
                               np.triu(Fd.QR.to_numpy())[:n, :n],
                               atol=1e-8)
    c = rng.standard_normal((m, m))
    for side in (Side.Left, Side.Right):
        for trans in (False, True):
            got = st.unmqr(side, F, M(c, 8), trans=trans).to_numpy()
            ref = st.unmqr(side, Fd, M(c, 8), trans=trans).to_numpy()
            np.testing.assert_allclose(got, ref, atol=1e-9,
                                       err_msg=f"{side} {trans}")
    # gels end-to-end through the fused factors
    b = rng.standard_normal((m, 2))
    X = st.gels(M(a, 8), M(b, 8), opts)
    np.testing.assert_allclose(X.to_numpy()[:n],
                               np.linalg.lstsq(a, b, rcond=None)[0],
                               rtol=1e-8, atol=1e-9)

def test_unmqr_explicit_q_input(rng):
    """A caller-constructed explicit-Q QRFactors still applies through
    unmqr by one matmul (the representation remains accepted on
    input)."""
    from slate_tpu.core.enums import Side
    from slate_tpu.linalg.qr import QRFactors

    m = 48
    a = rng.standard_normal((m, m))
    q_np, r_np = np.linalg.qr(a)
    F = QRFactors(M(r_np, 8), np.zeros((m,)), M(q_np, 8))
    c = rng.standard_normal((m, 3))
    got = st.unmqr(Side.Left, F, M(c, 8), trans=True).to_numpy()
    np.testing.assert_allclose(got, q_np.T @ c, atol=1e-10)


def test_gelqf_fused_method_passthrough(rng):
    """gelqf forwards MethodFactor.Fused into the dual QR (safe since
    round 3: every geqrf path keeps the packed contract unmlq needs);
    the wide-gels path stays correct."""
    from slate_tpu.core.methods import MethodFactor
    from slate_tpu.core.options import Option

    m, n = 16, 40
    a = rng.standard_normal((m, n))
    b = rng.standard_normal((m, 2))
    opts = {Option.MethodFactor: MethodFactor.Fused}
    X = st.gels(M(a, 8), M(b, 8), opts)
    x = X.to_numpy()[:n]
    np.testing.assert_allclose(a @ x, b, rtol=1e-8)
    np.testing.assert_allclose(x, np.linalg.lstsq(a, b, rcond=None)[0],
                               rtol=1e-7, atol=1e-9)


def test_geqrf_blocksize_option(rng):
    """Option.BlockSize overrides geqrf's algorithmic panel width
    without changing results — any width, divisible or not (the
    packed Householder format is blocking-independent)."""
    from slate_tpu.core.options import Option

    m, n = 96, 96
    a = rng.standard_normal((m, n))
    F0 = st.geqrf(M(a, 16))
    for bs in (24, 40):          # 40 does not divide the padded width
        F1 = st.geqrf(M(a, 16), {Option.BlockSize: bs})
        np.testing.assert_allclose(np.triu(F1.QR.to_numpy()),
                                   np.triu(F0.QR.to_numpy()),
                                   rtol=1e-11, atol=1e-12)
        c = rng.standard_normal((m, 2))
        got = st.unmqr(Side.Left, F1, M(c, 16), trans=True).to_numpy()
        ref = st.unmqr(Side.Left, F0, M(c, 16), trans=True).to_numpy()
        np.testing.assert_allclose(got, ref, rtol=1e-10, atol=1e-11)
