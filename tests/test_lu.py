"""LU family tests (reference test/test_gesv.cc residual style)."""

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import TiledMatrix


def M(a, nb=16):
    return TiledMatrix.from_dense(a, nb)


def wellcond(rng, n):
    a = rng.standard_normal((n, n))
    return a + n * np.eye(n) * 0.1


def test_getrf_reconstruct(rng):
    n = 48
    a = rng.standard_normal((n, n))
    F = st.getrf(M(a))
    lu = F.LU.to_numpy()
    L = np.tril(lu, -1) + np.eye(n)
    U = np.triu(lu)
    # P A = L U: apply recorded swaps to A
    pa = a.copy()
    piv = np.asarray(F.pivots)
    for j in range(n):
        pa[[j, piv[j]]] = pa[[piv[j], j]]
    np.testing.assert_allclose(L @ U, pa, rtol=1e-10, atol=1e-12)


def test_getrf_matches_scipy_pivots(rng):
    import scipy.linalg as sla
    n = 32
    a = rng.standard_normal((n, n))
    F = st.getrf(M(a, 8))
    lu_ref, piv_ref = sla.lu_factor(a)
    np.testing.assert_allclose(F.LU.to_numpy(), lu_ref, rtol=1e-9,
                               atol=1e-11)
    np.testing.assert_array_equal(np.asarray(F.pivots), piv_ref)


def test_gesv(rng):
    n, nrhs = 60, 5
    a = rng.standard_normal((n, n))
    b = rng.standard_normal((n, nrhs))
    F, X = st.gesv(M(a), M(b))
    x = X.to_numpy()
    resid = np.linalg.norm(b - a @ x) / (
        np.linalg.norm(a) * np.linalg.norm(x) * n * np.finfo(float).eps)
    assert resid < 50


def test_gesv_ragged(rng):
    n = 45   # not multiple of nb
    a = rng.standard_normal((n, n))
    b = rng.standard_normal((n, 3))
    _, X = st.gesv(M(a), M(b))
    np.testing.assert_allclose(a @ X.to_numpy(), b, rtol=1e-8)


def test_gesv_complex(rng):
    n = 24
    a = rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))
    b = rng.standard_normal((n, 2)) + 1j * rng.standard_normal((n, 2))
    _, X = st.gesv(M(a, 8), M(b, 8))
    np.testing.assert_allclose(a @ X.to_numpy(), b, rtol=1e-8)


def test_getrs_trans(rng):
    n = 30
    a = rng.standard_normal((n, n))
    b = rng.standard_normal((n, 2))
    F = st.getrf(M(a, 8))
    X = st.getrs(F, M(b, 8), trans=True)
    np.testing.assert_allclose(a.T @ X.to_numpy(), b, rtol=1e-8)


def test_getrs_trans_op_complex(rng):
    """Op.Trans (plain transpose) vs Op.ConjTrans for complex matrices
    (LAPACK 'T' vs 'C'); ADVICE round-1 low finding."""
    n = 24
    a = rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))
    a += 2 * np.eye(n)
    b = rng.standard_normal((n, 2)) + 1j * rng.standard_normal((n, 2))
    F = st.getrf(M(a, 8))
    Xt = st.getrs(F, M(b, 8), trans=st.Op.Trans)
    np.testing.assert_allclose(a.T @ Xt.to_numpy(), b, rtol=1e-8)
    Xc = st.getrs(F, M(b, 8), trans=st.Op.ConjTrans)
    np.testing.assert_allclose(a.conj().T @ Xc.to_numpy(), b, rtol=1e-8)


def test_getrs_mismatched_padding(rng):
    """A padded to more rows than B (different tile sizes): pivot vector
    must truncate to B's padded rows; ADVICE round-1 low finding."""
    n = 20
    a = wellcond(rng, n)
    b = rng.standard_normal((n, 3))
    F = st.getrf(M(a, 16))        # A padded to 32 rows
    X = st.getrs(F, M(b, 4))      # B padded to 20 rows
    np.testing.assert_allclose(a @ X.to_numpy(), b, rtol=1e-8)


def test_gesv_nopiv(rng):
    n = 40
    a = wellcond(rng, n) + 5 * np.eye(n)   # diagonally dominant enough
    b = rng.standard_normal((n, 2))
    _, X = st.gesv_nopiv(M(a), M(b))
    np.testing.assert_allclose(a @ X.to_numpy(), b, rtol=1e-7)


def test_getri(rng):
    n = 36
    a = rng.standard_normal((n, n)) + 2 * np.eye(n)
    F = st.getrf(M(a, 8))
    Ainv = st.getri(F).to_numpy()
    np.testing.assert_allclose(Ainv @ a, np.eye(n), atol=1e-8)


def test_gesv_mixed(rng):
    n = 40
    a = wellcond(rng, n)
    b = rng.standard_normal((n, 2))
    F, X, iters = st.gesv_mixed(M(a), M(b))
    # factor was computed in f32 (lo precision of f64)
    assert F.LU.dtype == np.float32
    assert int(iters) >= 0          # converged without fallback
    np.testing.assert_allclose(a @ X.to_numpy(), b, rtol=1e-9)


def test_gesv_mixed_gmres(rng):
    n = 32
    a = wellcond(rng, n)
    b = rng.standard_normal((n, 1))
    F, X, _ = st.gesv_mixed_gmres(M(a, 8), M(b, 8))
    np.testing.assert_allclose(a @ X.to_numpy(), b, rtol=1e-8)


def test_gesv_rbt(rng):
    n = 48
    a = wellcond(rng, n)
    b = rng.standard_normal((n, 2))
    _, X = st.gesv_rbt(M(a), M(b))
    np.testing.assert_allclose(a @ X.to_numpy(), b, rtol=1e-6)


def test_gbsv(rng):
    n, kl, ku = 40, 2, 3
    a = np.triu(np.tril(rng.standard_normal((n, n)), kl), -ku).T \
        + 4 * np.eye(n)
    A = st.BandMatrix(kl, ku, a, mb=8)
    b = rng.standard_normal((n, 2))
    F, X = st.gbsv(A, M(b, 8))
    np.testing.assert_allclose(A.to_numpy() @ X.to_numpy(), b, rtol=1e-8)


def test_apply_pivots_roundtrip(rng):
    import jax.numpy as jnp
    n = 20
    b = rng.standard_normal((n, 3))
    piv = np.arange(n, dtype=np.int32)
    piv[0], piv[5], piv[7] = 5, 12, 7
    B = M(b, 8)
    fwd = st.apply_pivots(jnp.asarray(piv), B)
    back = st.apply_pivots(jnp.asarray(piv), fwd, forward=False)
    np.testing.assert_allclose(back.to_numpy(), b)


def test_getrf_jit(rng):
    import jax
    n = 32
    a = rng.standard_normal((n, n))
    F = jax.jit(st.getrf)(M(a, 8))
    lu = F.LU.to_numpy()
    assert np.isfinite(lu).all()


def test_bf16_factor_routes_tiled(rng):
    # XLA's native LU/Cholesky don't implement bf16 (the mixed-precision
    # lo dtype on TPU); Auto must route such inputs to the Tiled path
    # instead of crashing in LuDecomposition (regression: ex06 on chip)
    import dataclasses

    import jax.numpy as jnp
    n = 32
    a = (rng.standard_normal((n, n)) + 3 * np.eye(n)).astype(np.float32)
    r = M(a).resolve()
    Ab = dataclasses.replace(r, data=r.data.astype(jnp.bfloat16))
    F = st.getrf(Ab)
    lu = np.asarray(F.LU.data, np.float32)
    assert np.isfinite(lu).all()
    from slate_tpu.core.methods import MethodFactor
    assert not MethodFactor.native_lu_dtype_ok(Ab.data.dtype)
    assert MethodFactor.select(
        Ab.data, MethodFactor.native_lu_dtype_ok(Ab.data.dtype)) \
        is MethodFactor.Tiled


def test_lu_scan_matches_unrolled(rng, monkeypatch):
    """Fixed-shape fori_loop LU (compile-time-safe form for huge nt)
    must reproduce the unrolled blocked loop bit-for-bit semantics
    (same pivots, same packed factor)."""
    import jax.numpy as jnp
    from slate_tpu.linalg import lu as lumod
    n, nb = 96, 8
    a = rng.standard_normal((n, n))
    aj = jnp.asarray(a)
    # lookahead=0: compare against the plain unrolled loop (the
    # reference path), not the pipelined default
    lu_ref, piv_ref = lumod._getrf_dense(aj, nb, pivot=True,
                                         lookahead=0)
    lu_s, piv_s = lumod._lu_scan(aj, nb, pivot=True)
    np.testing.assert_array_equal(np.asarray(piv_s), np.asarray(piv_ref))
    np.testing.assert_allclose(np.asarray(lu_s), np.asarray(lu_ref),
                               rtol=1e-12, atol=1e-13)
    # nopiv variant
    a2 = rng.standard_normal((n, n)) + n * np.eye(n)
    lu_ref, _ = lumod._getrf_dense(jnp.asarray(a2), nb, pivot=False)
    lu_s, _ = lumod._lu_scan(jnp.asarray(a2), nb, pivot=False)
    np.testing.assert_allclose(np.asarray(lu_s), np.asarray(lu_ref),
                               rtol=1e-10, atol=1e-11)


def test_lu_scan_threshold_route(rng, monkeypatch):
    """Above LU_SCAN_THRESHOLD block steps the Tiled LU takes the
    fixed-shape fori_loop form. Option.BlockSize pins the algorithmic
    blocking (the default policy floors it at 512, which would give
    nt=1 here and never reach the scan)."""
    from slate_tpu.core.options import Option
    from slate_tpu.core.methods import MethodFactor
    from slate_tpu.linalg import lu as lumod
    monkeypatch.setattr(lumod, "LU_SCAN_THRESHOLD", 4)
    n = 64
    a = rng.standard_normal((n, n)) + 0.2 * n * np.eye(n)
    b = rng.standard_normal((n, 2))
    F, X = st.gesv(M(a, 8), M(b, 8),
                   {Option.MethodFactor: MethodFactor.Tiled,
                    Option.BlockSize: 8})
    np.testing.assert_allclose(a @ X.to_numpy(), b, rtol=1e-9,
                               atol=1e-10)


def test_lu_scan_nondividing_blocksize_falls_back(rng, monkeypatch):
    """A user Option.BlockSize (or the _lu_nb default) that does not
    divide the padded N must not reach _lu_scan, whose fixed-shape
    dynamic_slice steps would clamp at the edge and silently corrupt
    the factorization (round-3 advisor finding: n=96 BlockSize=20 gave
    getrs residual ~3e8). The guard falls back to the storage tile
    size, which always divides the padded dims."""
    from slate_tpu.core.options import Option
    from slate_tpu.core.methods import MethodFactor
    from slate_tpu.linalg import lu as lumod
    monkeypatch.setattr(lumod, "LU_SCAN_THRESHOLD", 4)
    n = 96
    a = rng.standard_normal((n, n)) + 0.2 * n * np.eye(n)
    b = rng.standard_normal((n, 2))
    # nb=20 does not divide 96; nt=5 > patched threshold -> scan route
    F, X = st.gesv(M(a, 8), M(b, 8),
                   {Option.MethodFactor: MethodFactor.Tiled,
                    Option.BlockSize: 20})
    np.testing.assert_allclose(a @ X.to_numpy(), b, rtol=1e-9,
                               atol=1e-10)
    # the last-resort divisor fallback (no tile size available)
    assert lumod._scan_nb(96, 20) == 16
    assert lumod._scan_nb(97, 20) == 1
    # %8 preference for the Pallas-capped bf16 path, with plain
    # fallback when no %8 divisor exists
    assert lumod._scan_nb(96 * 5, 250, 8) == 240
    assert lumod._scan_nb(4, 3, 8) == 2


def test_getrf_lookahead_pipelined_matches_plain(rng, monkeypatch):
    """Option.Lookahead=1 routes the Tiled getrf through the
    software-pipelined loop (reference getrf.cc lookahead split);
    deferred-swap ordering must reproduce the plain loop exactly.
    The native-LU dtype gate is forced off so the test exercises the
    pipelined/plain pair (single-device native dtypes route to the
    carry form, which ignores lookahead by measured design)."""
    from slate_tpu.core.methods import MethodFactor
    from slate_tpu.core.options import Option
    monkeypatch.setattr(MethodFactor, "native_lu_dtype_ok",
                        staticmethod(lambda dt: False))

    for m, n in ((96, 96), (96, 120), (120, 96)):
        a = rng.standard_normal((m, n))
        A = st.Matrix(a, mb=16)
        # BlockSize pinned small: the default policy floors nb at 512,
        # which would make nt=1 and vacate the pipelined/plain pair
        base = {Option.MethodFactor: MethodFactor.Tiled,
                Option.BlockSize: 16}
        F0 = st.getrf(A, {**base, Option.Lookahead: 0})
        F1 = st.getrf(A, {**base, Option.Lookahead: 1})
        np.testing.assert_array_equal(np.asarray(F1.pivots),
                                      np.asarray(F0.pivots))
        np.testing.assert_allclose(F1.LU.to_numpy(), F0.LU.to_numpy(),
                                   rtol=1e-12, atol=1e-13)
        # end-to-end solve through the pipelined factors
        if m == n:
            b = rng.standard_normal((m, 2))
            X = st.getrs(F1, st.Matrix(b, mb=16))
            np.testing.assert_allclose(a @ X.to_numpy(), b, rtol=1e-8,
                                       atol=1e-8)


def test_getrf_carry_rectangular(rng):
    """The single-device carry driver handles tall and wide shapes,
    including ragged (non-tile-multiple) logical sizes. Verification
    happens at the PADDED level with the full pivot vector: the
    identity-padded columns' unit pivots wander under earlier row
    swaps, so pad-column pivot entries legitimately permute logical
    rows — pivots and factors are self-consistent as a padded pair
    (the contract getrs/apply_pivots consume), not truncated to the
    logical reflector count."""
    from slate_tpu.core.tiles import pad_diag_identity
    import jax.numpy as jnp

    from slate_tpu.core.options import Option
    # BlockSize=32 -> nt > 1 so the carry loop (not the single-panel
    # degenerate case) actually runs at these test sizes
    for m, n in ((120, 72), (72, 120), (96, 96)):
        a = rng.standard_normal((m, n))
        F = st.getrf(M(a, 16), {Option.BlockSize: 32})
        lu = np.asarray(F.LU.data)              # padded storage
        Mp, Np = lu.shape
        kp = min(Mp, Np)
        L = np.tril(lu[:, :kp], -1) + np.eye(Mp, kp)
        U = np.triu(lu[:kp])
        pa = np.zeros((Mp, Np))
        pa[:m, :n] = a
        pa = np.asarray(pad_diag_identity(jnp.asarray(pa), m, n)).copy()
        piv = np.asarray(F.pivots)
        for j in range(kp):
            pa[[j, piv[j]]] = pa[[piv[j], j]]
        np.testing.assert_allclose(L @ U, pa, rtol=1e-10, atol=1e-11)


# -- the carry form's compiled pieces (PR 30) ------------------------------

def _finish_twin(panels, perms, urows, pivs, nb, kmax, M, N):
    """What `_getrf_carry` did from the host before its finish was one
    program, in numpy: for each panel the later steps' permutations
    composed one by one (quadratic in the step count), the panel
    gathered through the result, the packed factor assembled, the
    pivots offset and joined."""
    nt = len(panels)
    out = np.zeros((M, N), panels[0].dtype)
    for k in range(nt):
        q = np.arange(panels[k].shape[0])
        for j in range(k + 1, nt):
            off = (j - k) * nb
            q = np.concatenate([q[:off], q[off:][perms[j]]])
        out[k * nb:, k * nb:k * nb + panels[k].shape[1]] = panels[k][q]
    for k, strip in enumerate(urows):
        out[k * nb:k * nb + strip.shape[0],
            min((k + 1) * nb, kmax):] = strip
    return out, np.concatenate([k * nb + p for k, p in enumerate(pivs)])


@pytest.mark.parametrize("nt", [2, 3, 8])
@pytest.mark.parametrize("shape", ["square", "tall", "wide", "ragged"])
def test_carry_finish_matches_the_quadratic_loop(rng, shape, nt):
    """`lu._carry_finish` composes the suffix permutations by a
    backward recurrence inside one program; factor and pivots are
    bitwise what the step-by-step loop gave, on lists of the shapes
    `_getrf_carry` hands it."""
    import jax.numpy as jnp
    from slate_tpu.linalg import lu as lumod
    nb = 8
    kmax = nt * nb - (3 if shape == "ragged" else 0)
    M = kmax + (13 if shape == "tall" else 0)
    N = kmax + (13 if shape == "wide" else 0)
    panels, perms, urows, pivs = [], [], [], []
    for k in range(nt):
        k1 = min((k + 1) * nb, kmax)
        m, w = M - k * nb, k1 - k * nb
        panels.append(rng.standard_normal((m, w)).astype(np.float32))
        perms.append(rng.permutation(m).astype(np.int32))
        pivs.append(rng.integers(0, m, w).astype(np.int32))
        if k1 < N:
            urows.append(
                rng.standard_normal((w, N - k1)).astype(np.float32))
    want, want_piv = _finish_twin(panels, perms, urows, pivs, nb, kmax,
                                  M, N)
    dev = [[jnp.asarray(x) for x in xs]
           for xs in (panels, perms, urows, pivs)]
    out, piv = lumod._carry_finish(*dev, nb=nb, kmax=kmax, M=M, N=N)
    assert out.dtype == want.dtype and piv.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), want)
    np.testing.assert_array_equal(np.asarray(piv), want_piv)


@pytest.fixture
def obs_on():
    """Event bus + metrics on, reset around the test."""
    from slate_tpu import obs
    from slate_tpu.obs import metrics
    obs.enable()
    obs.clear()
    metrics.reset()
    yield obs
    obs.disable()
    obs.clear()
    metrics.reset()


def _carry_case(rng):
    from slate_tpu.core.options import Option
    a = rng.standard_normal((256, 200)).astype(np.float32)
    return st.Matrix(a, mb=64), {Option.BlockSize: 64}


def test_getrf_rerun_compiles_nothing(rng, obs_on):
    """A second carry-form getrf of a shape traces and compiles
    nothing (the tier-1 twin of benchmarks/run.py's exit 3, as
    test_stream.py::test_stream_rerun_compiles_nothing): its programs
    are module-level and keyed on shapes, so their caches outlive the
    call."""
    import jax
    from slate_tpu.obs import metrics
    A, opts = _carry_case(rng)

    def jit_counters():
        c = metrics.snapshot()["counters"]
        return {k: v for k, v in c.items() if k.startswith("jit.")}

    F0 = st.getrf(A, opts)
    before, recompiled = jit_counters(), metrics.recompiles()
    assert before.get("jit.backend_compile_seconds", 0.0) > 0.0
    F1 = st.getrf(A, opts)
    assert jit_counters() == before
    assert metrics.recompiles() == recompiled
    np.testing.assert_array_equal(np.asarray(F1.LU.data),
                                  np.asarray(F0.LU.data))
    jax.jit(lambda x: x * 3.25 + 1.0)(np.ones(7, np.float32))
    assert jit_counters()["jit.backend_compile_seconds"] \
        > before["jit.backend_compile_seconds"]


def test_getrf_carry_spans_hold_one_program_each(rng, obs_on,
                                                 dispatches_under):
    """On a second call (nothing left to trace) every step span of
    the carry form still opens at run time, and the host dispatches
    one compiled program under each: `getrf::reorder` holds
    `_carry_finish` alone, where it held some thirty eager index
    operations a panel. Read from the profiler's host plane
    (conftest's `dispatches_under`)."""
    A, opts = _carry_case(rng)        # 256 x 200 at nb 64: 4 steps
    st.getrf(A, opts)
    obs_on.clear()

    def factor():
        st.getrf(A, opts).LU.data.block_until_ready()

    want = {"getrf::panel": 4, "getrf::pivots": 4, "getrf::update": 3,
            "getrf::reorder": 1}
    held = dispatches_under(factor, set(want))
    steps = [e.name for e in obs_on.bus_events(cat="step")]
    assert {n: steps.count(n) for n in want} == want
    assert {n: len(held[n]) for n in want} == want
    assert held["getrf::reorder"] == [["_carry_finish"]]
    assert held["getrf::panel"] == [["_carry_panel"]] * 4
    # the last step of a tall matrix has no columns to its right
    assert held["getrf::pivots"] == [["_carry_swap"]] * 3 + [[]]
    assert held["getrf::update"] == [["_carry_update"]] * 3


def test_getrf_blocksize_option(rng):
    """Option.BlockSize overrides the algorithmic panel width without
    changing results (the blocking is a schedule knob, not a numerics
    knob)."""
    from slate_tpu.core.options import Option
    n = 96
    a = rng.standard_normal((n, n))
    F0 = st.getrf(M(a, 16))
    F1 = st.getrf(M(a, 16), {Option.BlockSize: 32})
    np.testing.assert_array_equal(np.asarray(F0.pivots),
                                  np.asarray(F1.pivots))
    np.testing.assert_allclose(F0.LU.to_numpy(), F1.LU.to_numpy(),
                               rtol=1e-11, atol=1e-12)


def test_bf16_permute_rows_detour(rng):
    """Sub-f32 row gathers detour through f32 (lu._permute_rows): this
    libtpu's bf16 gather fusion dies in compile at n>=8192 panels
    (PERF.md round-4c). The detour must be value-exact and the whole
    bf16 factorization must still solve correctly."""
    import dataclasses

    import jax.numpy as jnp

    from slate_tpu.linalg.lu import _permute_rows
    x = jnp.asarray(rng.standard_normal((64, 16)), jnp.bfloat16)
    perm = jnp.asarray(rng.permutation(64))
    assert (np.asarray(_permute_rows(x, perm), np.float32)
            == np.asarray(x, np.float32)[np.asarray(perm)]).all()
    # end to end: a bf16 gesv through the Tiled route with pivoting
    n = 96
    a = (rng.standard_normal((n, n)) + 0.3 * n * np.eye(n)).astype(
        np.float32)
    b = rng.standard_normal((n, 2)).astype(np.float32)
    r = M(a).resolve()
    Ab = dataclasses.replace(r, data=r.data.astype(jnp.bfloat16))
    F = st.getrf(Ab)
    rb = M(b).resolve()
    Bb = dataclasses.replace(rb, data=rb.data.astype(jnp.bfloat16))
    x_lo = st.getrs(F, Bb)
    got = np.asarray(x_lo.to_numpy(), np.float32)
    ref = np.linalg.solve(a.astype(np.float64), b)
    # bf16 factor: loose tolerance, but the PIVOTED structure must be
    # right (a wrong permutation produces garbage, not 1e-2-level error)
    assert np.abs(got - ref).max() / np.abs(ref).max() < 5e-2
