"""Out-of-core streaming drivers (linalg/ooc.py): the streamed panel
schedule must reproduce the in-core results exactly up to roundoff,
with HBM residency bounded by one panel (exercised here with panels
much smaller than the matrix, so every code path — multi-visit
left-looking updates, ragged last panel — runs)."""

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu.linalg.ooc import gemm_ooc, potrf_ooc


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


def test_potrf_ooc_matches_incore(rng):
    n = 384
    x = rng.standard_normal((n, n))
    a = x @ x.T / n + 4.0 * np.eye(n)
    L = potrf_ooc(a, panel_cols=128)
    r = a - L @ L.T
    assert np.abs(r).max() / np.abs(a).max() < 1e-12
    assert np.allclose(L, np.tril(L))


def test_potrf_ooc_ragged_panel(rng):
    n = 300                       # 300 = 2*128 + 44: ragged last panel
    x = rng.standard_normal((n, n))
    a = x @ x.T / n + 4.0 * np.eye(n)
    L = potrf_ooc(a, panel_cols=128)
    ref = np.linalg.cholesky(a)
    assert np.abs(L - ref).max() < 1e-10


def test_potrf_ooc_single_panel(rng):
    n = 64
    x = rng.standard_normal((n, n))
    a = x @ x.T / n + 2.0 * np.eye(n)
    L = potrf_ooc(a, panel_cols=256)      # whole matrix in one panel
    assert np.abs(a - L @ L.T).max() < 1e-12


def test_getrf_ooc_matches_incore(rng):
    """Streamed left-looking LU must match the in-core factorization
    up to roundoff: same pivots, residual-exact solve."""
    from slate_tpu.linalg.ooc import getrf_ooc, getrs_ooc
    n = 384
    a = rng.standard_normal((n, n)) + 0.2 * n * np.eye(n)
    lu, ipiv = getrf_ooc(a, panel_cols=128)
    # P A = L U reconstruction
    L = np.tril(lu, -1) + np.eye(n)
    U = np.triu(lu)
    from slate_tpu.linalg.ooc import _swaps_to_perm
    perm = _swaps_to_perm(ipiv, n)
    assert np.abs(a[perm] - L @ U).max() / np.abs(a).max() < 1e-12
    # streamed solve
    b = rng.standard_normal((n, 3))
    x = getrs_ooc(lu, ipiv, b, panel_cols=128)
    assert np.abs(a @ x - b).max() < 1e-9


def test_getrf_ooc_matches_incore_pivots(rng):
    """Panel-confined pivoting sees exactly the rows in-core partial
    pivoting would search, so the pivot SEQUENCE matches the in-core
    driver's."""
    from slate_tpu.linalg.ooc import getrf_ooc
    n = 256
    a = rng.standard_normal((n, n))
    lu, ipiv = getrf_ooc(a, panel_cols=64)
    F = st.getrf(st.Matrix(a, mb=64))
    np.testing.assert_array_equal(ipiv, np.asarray(F.pivots)[:n])
    np.testing.assert_allclose(lu, np.asarray(F.LU.to_numpy()),
                               rtol=1e-10, atol=1e-12)


def test_getrf_ooc_ragged_and_rect(rng):
    from slate_tpu.linalg.ooc import getrf_ooc, _swaps_to_perm
    # ragged last panel
    n = 300
    a = rng.standard_normal((n, n))
    lu, ipiv = getrf_ooc(a, panel_cols=128)
    L = np.tril(lu, -1) + np.eye(n)
    perm = _swaps_to_perm(ipiv, n)
    assert np.abs(a[perm] - L @ np.triu(lu)).max() < 1e-10
    # wide rectangle (kmax inside a panel)
    m, n2 = 160, 300
    a2 = rng.standard_normal((m, n2))
    lu2, ipiv2 = getrf_ooc(a2, panel_cols=128)
    L2 = np.tril(lu2[:, :m], -1) + np.eye(m)
    perm2 = _swaps_to_perm(ipiv2, m)
    assert np.abs(a2[perm2] - L2 @ np.triu(lu2)).max() < 1e-10
    # tall rectangle
    m3, n3 = 300, 160
    a3 = rng.standard_normal((m3, n3))
    lu3, ipiv3 = getrf_ooc(a3, panel_cols=128)
    L3 = np.tril(lu3, -1)[:, :n3] + np.eye(m3, n3)
    perm3 = _swaps_to_perm(ipiv3, m3)
    assert np.abs(a3[perm3] - L3 @ np.triu(lu3[:n3])).max() < 1e-10


def test_geqrf_ooc_matches_incore(rng):
    """Streamed left-looking QR: packed factor reconstructs A and
    matches the in-core geqrf driver's R up to sign."""
    from slate_tpu.linalg.ooc import geqrf_ooc, unmqr_ooc
    m, n = 384, 384
    a = rng.standard_normal((m, n))
    qr_p, taus = geqrf_ooc(a, panel_cols=128)
    # Q (R-embedded) reconstruction: A == Q R
    R = np.triu(qr_p)[:n]
    QR = unmqr_ooc(qr_p, taus, np.vstack([R, np.zeros((m - n, n))]),
                   trans=False, panel_cols=128)
    assert np.abs(QR - a).max() / np.abs(a).max() < 1e-12
    # R matches in-core geqrf's R up to column signs
    F = st.geqrf(st.Matrix(a, mb=128))
    R_ref = np.triu(np.asarray(F.QR.to_numpy()))[:n]
    s = np.sign(np.diag(R)) * np.sign(np.diag(R_ref))
    assert np.abs(R - s[:, None] * R_ref).max() < 1e-9


def test_gels_ooc_tall_skinny(rng):
    from slate_tpu.linalg.ooc import gels_ooc
    m, n, nrhs = 500, 96, 2
    a = rng.standard_normal((m, n))
    b = rng.standard_normal((m, nrhs))
    _, x = gels_ooc(a, b, panel_cols=48)
    ref, *_ = np.linalg.lstsq(a, b, rcond=None)
    assert np.abs(x - ref).max() < 1e-8
    # wide input is rejected (the R sweep indexes n factor rows)
    with pytest.raises(Exception, match="tall"):
        gels_ooc(rng.standard_normal((96, 500)),
                 rng.standard_normal((96, 2)))


def test_geqrf_ooc_wide(rng):
    """m < n: trailing panels past kmax receive visits only."""
    from slate_tpu.linalg.ooc import geqrf_ooc, unmqr_ooc
    m, n = 160, 300
    a = rng.standard_normal((m, n))
    qr_p, taus = geqrf_ooc(a, panel_cols=128)
    R = np.triu(qr_p)
    QR = unmqr_ooc(qr_p, taus, R, trans=False, panel_cols=128)
    assert np.abs(QR - a).max() < 1e-10


def test_gemm_ooc_matches_numpy(rng):
    m, k, n = 333, 96, 64
    a = rng.standard_normal((m, k))
    b = rng.standard_normal((k, n))
    c = rng.standard_normal((m, n))
    got = gemm_ooc(2.0, a, b, -0.5, c, row_panel=100)
    ref = 2.0 * a @ b - 0.5 * c
    assert np.abs(got - ref).max() < 1e-10


def test_potrs_ooc_matches_numpy(rng):
    """Streamed Cholesky solve from the streamed factor: forward
    non-unit sweep + conjugate-transposed backward sweep, panels much
    smaller than n so multi-panel corrections run."""
    from slate_tpu.linalg.ooc import posv_ooc, potrf_ooc, potrs_ooc
    n, nrhs = 300, 3
    x = rng.standard_normal((n, n))
    a = x @ x.T / n + 4.0 * np.eye(n)
    b = rng.standard_normal((n, nrhs))
    L = potrf_ooc(a, panel_cols=128)
    got = potrs_ooc(L, b, panel_cols=128)
    ref = np.linalg.solve(a, b)
    assert np.abs(got - ref).max() / np.abs(ref).max() < 1e-10
    # bundled driver agrees
    L2, x2 = posv_ooc(a, b, panel_cols=128)
    assert np.abs(L2 - L).max() == 0
    assert np.abs(x2 - got).max() < 1e-12


def test_potrs_ooc_single_panel(rng):
    from slate_tpu.linalg.ooc import potrf_ooc, potrs_ooc
    n = 64
    x = rng.standard_normal((n, n))
    a = x @ x.T / n + 2.0 * np.eye(n)
    b = rng.standard_normal((n, 2))
    got = potrs_ooc(potrf_ooc(a, panel_cols=256), b, panel_cols=256)
    assert np.abs(got - np.linalg.solve(a, b)).max() < 1e-11


def test_potrf_ooc_invert_route(rng, monkeypatch):
    """Large-panel safety valve: when the below-block solve's expander
    temps would blow HBM, _panel_factor inverts the diag block and
    multiplies instead. Forced here by zeroing the cap; results must
    match the solve route to roundoff."""
    from slate_tpu.linalg import ooc
    n = 300
    x = rng.standard_normal((n, n))
    a = x @ x.T / n + 4.0 * np.eye(n)
    b = rng.standard_normal((n, 2))
    ref = ooc.potrf_ooc(a, panel_cols=128)
    ref_x = ooc.potrs_ooc(ref, b, panel_cols=128)
    # cap -1, not 0: solve_temps_bytes returns 0 for triangles
    # narrower than 128 and the gate is strict '>', so a zero cap
    # would let the ragged last panel keep the direct-solve route
    monkeypatch.setattr(ooc, "OOC_SOLVE_TEMP_CAP", -1)
    for k in (ooc._panel_factor, ooc._lu_visit, ooc._chol_back_visit):
        k.clear_cache()
    got = ooc.potrf_ooc(a, panel_cols=128)
    x = ooc.potrs_ooc(got, b, panel_cols=128)
    for k in (ooc._panel_factor, ooc._lu_visit, ooc._chol_back_visit):
        k.clear_cache()
    assert np.abs(got - ref).max() < 1e-10
    assert np.abs(a - got @ got.T).max() / np.abs(a).max() < 1e-12
    assert np.abs(x - ref_x).max() < 1e-9


def test_getrf_ooc_invert_route(rng, monkeypatch):
    """The LU visit's U-strip solve takes the same invert-then-matmul
    valve at OOC panel widths; forced via the zeroed cap, the whole
    factorization must still match in-core to roundoff."""
    from slate_tpu.linalg import ooc
    n = 320
    a = rng.standard_normal((n, n)) + 0.2 * n * np.eye(n)
    b = rng.standard_normal((n, 2))
    ref_lu, ref_piv = ooc.getrf_ooc(a, panel_cols=128)
    monkeypatch.setattr(ooc, "OOC_SOLVE_TEMP_CAP", -1)  # see potrf twin
    for k in (ooc._lu_visit, ooc._lu_back_visit):
        k.clear_cache()
    lu, piv = ooc.getrf_ooc(a, panel_cols=128)
    x = ooc.getrs_ooc(lu, piv, b, panel_cols=128)
    for k in (ooc._lu_visit, ooc._lu_back_visit):
        k.clear_cache()
    assert np.array_equal(piv, ref_piv)
    assert np.abs(lu - ref_lu).max() < 1e-9
    assert np.abs(a @ x - b).max() < 1e-9


# -- the partial-pivot stream against the walk it replaced (PR 47) --------

def _host_order_getrf(a, w, incore_nb=1024):
    """The walk `getrf_ooc`'s partial body made until PR 47, kept as
    the oracle: the host store in CURRENT row order at every step, so
    the input panel is gathered on the host through the running
    permutation and every written panel's rows k0: are rewritten
    after each panel's pivots; around the same `_lu_visit` and
    `_lu_panel_factor`. No engine, no cache, no thread."""
    import jax.numpy as jnp
    from slate_tpu.linalg import ooc
    m, n = a.shape
    kmax = min(m, n)
    perm, out = np.arange(m), np.empty_like(a)
    ipiv = np.empty((kmax,), np.int64)
    for k0 in range(0, n, w):
        k1 = min(k0 + w, n)
        S = jnp.asarray(np.take(a[:, k0:k1], perm, axis=0))
        for j0 in range(0, min(k0, kmax), w):
            S = ooc._lu_visit(S, jnp.asarray(out[:, j0:min(j0 + w, kmax)]),
                              j0)
        if k0 >= kmax:
            out[:, k0:k1] = np.asarray(S)       # past kmax: all U
            continue
        wf = min(k1, kmax) - k0
        packed, piv = ooc._lu_panel_factor(S[:, :wf], k0,
                                           min(incore_nb, max(wf, 1)))
        piv = np.asarray(piv)
        lperm = ooc._swaps_to_perm(piv, m - k0)
        out[k0:, :k0] = out[k0:, :k0][lperm]    # the fixup
        perm[k0:] = perm[k0:][lperm]
        ipiv[k0:k0 + wf] = k0 + piv
        out[:k0, k0:k1] = np.asarray(S[:k0])
        out[k0:, k0:k0 + wf] = np.asarray(packed[:m - k0])
        if wf < k1 - k0:                        # kmax inside the panel
            rest = S[k0:, wf:][jnp.asarray(lperm)]
            out[k0:k0 + wf, k0 + wf:k1] = np.asarray(
                ooc._unit_lower_solve_capped(packed[:wf, :wf], rest[:wf]))
    return out, ipiv


#: (m, n, w): eight square panels; tall; wide with kmax inside the
#: fifth panel and five all-U panels after it; n no multiple of w
_SHAPES = {"square": (256, 256, 32), "tall": (320, 160, 32),
           "wide": (144, 320, 32), "ragged": (200, 200, 48)}
#: panels of the factor the cache may hold: none, five of the
#: square's eight (as in the cell `stream-gesv`) and three of the
#: others' five, all
_RESIDENT = {"off": lambda nf: 0, "partly": lambda nf: max(nf - 3, 3),
             "fully": lambda nf: 64}


def _hpl_like(shape, name):
    """Uniform(-0.5, 0.5), f32: every panel's pivots leave the panel."""
    m, n, w = _SHAPES[shape]
    r = np.random.default_rng([len(name)] + list(name.encode()))
    return (r.random((m, n), dtype=np.float32) - np.float32(0.5)), w


@pytest.mark.parametrize("resident", sorted(_RESIDENT))
@pytest.mark.parametrize("shape", sorted(_SHAPES))
def test_getrf_ooc_partial_is_the_host_order_walk_bitwise(shape, resident):
    """Input panels staged as they lie and permuted on the chip,
    factor panels written once and gathered at each visit through
    r = inv(P_j)[P_now], one repair at the end: exact gathers around
    the same two programs, so `lu` and `ipiv` are the oracle's bit
    for bit, whatever the cache holds."""
    from slate_tpu.linalg import ooc, stream
    a, w = _hpl_like(shape, shape + resident)
    m, n = a.shape
    nf = -(-min(m, n) // w)                     # factor panels
    want_lu, want_piv = _host_order_getrf(a, w)
    lu, ipiv = ooc.getrf_ooc(
        a, panel_cols=w, pivot="partial",
        cache_budget_bytes=_RESIDENT[resident](nf) * m * w * 4)
    assert lu.dtype == a.dtype and lu.tobytes() == want_lu.tobytes()
    assert np.array_equal(ipiv, want_piv)
    s = stream.last_stats()
    assert s["invalidations"] == 0
    moved = nf - 1              # the panels the repair reorders: all
    visits = sum(min(k, nf) for k in range(-(-n // w)))
    if resident == "off":
        assert (s["hits"], s["misses"]) == (0, 0)
    elif resident == "fully":
        # every visit and every repaired panel served from the chip
        assert (s["hits"], s["misses"]) == (visits + moved, 0)
    else:
        assert s["hits"] + s["misses"] == visits + moved
        assert s["hits"] > 0 and s["misses"] > 0


@pytest.mark.parametrize("resident", sorted(_RESIDENT))
def test_getrf_ooc_partial_second_call_compiles_nothing(resident):
    """The gathers, the column and the repair's shapes are those of
    the first call: a second matrix of the shape compiles nothing."""
    from benchmarks.lib.compiles import Compiles
    from slate_tpu.linalg import ooc
    (a, w), (b, _) = _hpl_like("square", "first"), _hpl_like("square", "2nd")
    budget = _RESIDENT[resident](8) * a.shape[0] * w * 4
    ooc.getrf_ooc(a, panel_cols=w, pivot="partial", cache_budget_bytes=budget)
    comp = Compiles()
    s0 = comp.snap()
    ooc.getrf_ooc(b, panel_cols=w, pivot="partial", cache_budget_bytes=budget)
    assert comp.since(s0)["programs"] == 0
