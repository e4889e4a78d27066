"""Hermitian eigensolvers (reference src/heev.cc, hegv.cc, hegst.cc,
he2hb.cc, hb2st.cc, sterf.cc, steqr2.cc, stedc*.cc; SURVEY §3.5).

TPU-native design. The reference pipeline is:
    heev = he2hb (full->band, panel QR + two-sided updates)
         + hb2st (band->tridiagonal bulge chasing — sequential sweeps,
           "currently run on a single node", heev.cc:117)
         + steqr2/stedc (tridiagonal QR iteration / divide & conquer)
         + two back-transforms (unmtr_hb2st, unmtr_he2hb).
Bulge chasing is a latency-bound wavefront with O(n^2 b) tiny dependent
steps — the worst possible shape for a systolic MXU. The TPU-native
replacement with the same contract (eigenvalues + optional vectors of a
Hermitian matrix) is XLA's QDWH-based spectral divide & conquer
(`jax.lax.linalg.eigh`): polar-decomposition iterations built entirely
from large matmuls, compiling to MXU-saturating code and partitioning
over the mesh under SPMD. That is what `heev` uses. The two-stage names
(he2hb, hb2st, sterf, steqr2, stedc) remain as API entry points for
pipeline parity; he2hb/hb2st currently reduce via Householder
tridiagonalization on the gathered matrix (the reference likewise gathers
the band for stage 2, heev.cc:115).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.enums import Diag, MatrixType, Norm, Side, Uplo
from ..core.exceptions import slate_assert
from ..core.methods import MethodEig
from ..core.options import Option, OptionsLike, get_option
from ..core.tiles import TiledMatrix, ceil_div
from ..obs.events import instrument_driver
from ..ops.householder import reflect as _reflect
from .blas3 import _store, trsm
from .chol import potrf
from .spectral_dc import SPECTRAL_DC_MIN_N  # noqa: F401  (its old home)


class EigResult(NamedTuple):
    values: jax.Array                     # (n,) real ascending
    vectors: Optional[TiledMatrix]        # columns are eigenvectors


@instrument_driver("heev")
def heev(A: TiledMatrix, opts: OptionsLike = None,
         want_vectors: bool = True) -> EigResult:
    """Hermitian eigendecomposition (reference src/heev.cc, slate.hh:1094;
    syev alias :1115).

    MethodEig routes the solve (reference heev.cc:150-162 choosing
    steqr2 vs stedc): the default path is spectral divide & conquer —
    on the chip, for a concrete real matrix above SPECTRAL_DC_MIN_N,
    the in-house one (linalg/spectral_dc.py: a host agenda over
    per-bucket programs, which returns once the last split's sizes
    are read), else XLA's QDWH eigh (module doc: smaller or complex
    matrices, a caller's jit, and other backends unless a tune entry
    written there says otherwise); QRIteration runs the full reference pipeline
    he2hb -> hb2st -> steqr2 with the two back-transforms, DC the
    same with the Cuppen tridiagonal solver. When the caller leaves
    the method on Auto, a measured tune-cache entry (tune/select.py)
    may route it instead; cold cache keeps today's Auto behavior.

    Spans `heev::prep`, `heev::split` (bucket, size), `heev::agenda`
    (the host's read of a split's sizes), `heev::leaf`,
    `heev::vectors`; the root span carries the route (`method`,
    `form`, `leaf`, `buckets`); counters `heev.solves`,
    `heev.splits`, `heev.leaves`, `heev.polar_iters`,
    `heev.unconverged`, `heev.split_rows_true`,
    `heev.split_rows_padded`."""
    slate_assert(A.mtype in (MatrixType.Hermitian, MatrixType.Symmetric,
                             MatrixType.HermitianBand),
                 "heev: A must be Hermitian/symmetric")
    method = get_option(opts, Option.MethodEig, MethodEig.Auto)
    if method is MethodEig.Auto:
        from ..tune.select import tuned_method
        cached = tuned_method("heev", "eig", opts=opts,
                              option=Option.MethodEig,
                              n=A.shape[0], dtype=A.dtype)
        if cached is not None and cached is not MethodEig.Auto:
            method = cached
    if method is MethodEig.QRIteration:
        return _heev_two_stage(A, opts, want_vectors, use_dc=False)
    if method is MethodEig.DC:
        # staged pipeline with the Cuppen divide & conquer tridiagonal
        # solver (reference stedc); Auto stays on the fused QDWH path
        return _heev_two_stage(A, opts, want_vectors, use_dc=True)
    from ..obs import metrics as obs_metrics
    from ..obs.events import note
    from ..utils.trace import phases
    from . import spectral_dc
    ph = phases(opts)
    obs_metrics.inc("heev.solves")
    with ph("heev::prep"):
        a = A.to_dense()
        # the routing threshold and the leaf size are `svd`'s too, so
        # `spectral_dc.route` reads them for both drivers
        leaf = spectral_dc.route(a, opts)
    if leaf is not None:
        # the in-house spectral D&C (linalg/spectral_dc.py): same
        # QDWH-family algorithm as jax's eigh but with the all-
        # Cholesky polar and no padded-copy agenda — measured faster
        # on v5e above the threshold (PERF.md "Round-5: in-house
        # spectral divide & conquer"). Real dtypes only: the TPU
        # backend's Jacobi leaf solver does not implement complex.
        # Concrete arrays only: a host agenda dispatches its splits
        # and reads their sizes, so it returns when the last split
        # has been read and not at once; under a caller's jit the
        # solve is XLA's eigh below.
        note(method="spectral_dc", **spectral_dc.route_note(a.shape[0], leaf))
        w, v, dc_ok = spectral_dc.eigh_dc(a, leaf=leaf)  # ascending already
        # the agenda has read every split's converged flag on the
        # host by now (it reads each split's sizes anyway), so an
        # unconverged sign iteration is always surfaced
        if not dc_ok:
            import warnings
            warnings.warn(
                "heev: a spectral-D&C split's polar (sign) "
                "iteration hit its iteration cap without "
                "converging; eigenpairs may be degraded "
                "(polar.py capped-weight schedule)", stacklevel=2)
    else:
        note(method="xla_eigh", form="native")
        v, w = jax.lax.linalg.eigh(a)  # QDWH D&C (see module doc)
        order = jnp.argsort(w)
        w = w[order]
        v = v[:, order]
    if not want_vectors:
        return EigResult(w, None)
    with ph("heev::vectors"):
        r = A.resolve()
        V = TiledMatrix.from_dense(v, r.mb, r.nb)
    return EigResult(w, V)


def _heev_two_stage(A: TiledMatrix, opts, want_vectors: bool,
                    use_dc: bool) -> EigResult:
    """The staged reference pipeline (heev.cc): he2hb, hb2st, then the
    tridiagonal solver with the two-step back-transform
    (unmtr_hb2st + unmtr_he2hb, heev.cc:179-184). Eigenvalues-only
    skips both transform accumulations (the pipeline's dominant
    matmuls)."""
    from ..utils.trace import phases
    ph = phases(opts)
    with ph("heev::he2hb"):
        Band, Q1 = he2hb(A, opts, want_q=want_vectors)
    with ph("heev::hb2st"):
        tri = hb2st(Band, opts, want_q=want_vectors)
    if not want_vectors:
        with ph("heev::sterf"):
            return EigResult(sterf(tri.d, tri.e, opts), None)
    solver = stedc if use_dc else steqr2
    # this phase composes the stage-1 back-transform (unmtr_he2hb) with
    # the accumulated stage-2 rotations; the reference's unmtr_hb2st
    # application happens inside hb2st's Q accumulation above
    with ph("heev::unmtr_he2hb"):
        if tri.Q is not None:
            Qfull = unmtr_he2hb(Q1, tri.Q, opts)
        else:
            Qfull = Q1
    with ph("heev::stedc" if use_dc else "heev::steqr2"):
        w, V = solver(tri.d, tri.e, Qfull, opts)
    return EigResult(w, V)


def syev(A: TiledMatrix, opts: OptionsLike = None,
         want_vectors: bool = True) -> EigResult:
    """Reference slate.hh:1115."""
    return heev(A, opts, want_vectors)


def eig_vals(A: TiledMatrix, opts: OptionsLike = None):
    """Simplified-API name (simplified_api.hh:695-800)."""
    return heev(A, opts, want_vectors=False).values


def _hegst_blocked_lower(a: jax.Array, l: jax.Array, nb: int,
                         grid=None) -> jax.Array:
    """Blocked two-sided reduction C = L^-1 A L^-H in nb-panels —
    the reference's blocked transform (src/hegst.cc; LAPACK dsygst
    itype=1 Lower block structure: sygs2 diag, two half-symm A21
    corrections around the her2k trailing update, trsm with the
    trailing triangle). The her2k trailing update is the distributable
    bulk and carries the grid sharding constraint; the whole-matrix
    two-solve form cannot shard (XLA's TriangularSolve gathers), which
    is why the mesh path needs this shape."""
    from ..parallel.sharding import constrain
    HI = jax.lax.Precision.HIGHEST
    n = a.shape[0]
    for k0 in range(0, n, nb):
        k1 = min(k0 + nb, n)
        A11 = a[k0:k1, k0:k1]
        L11 = l[k0:k1, k0:k1]
        # diag block: A11 <- L11^-1 A11 L11^-H (sygs2 role)
        t = jax.lax.linalg.triangular_solve(
            L11, A11, left_side=True, lower=True)
        A11 = jax.lax.linalg.triangular_solve(
            L11, t.conj().T, left_side=True, lower=True).conj().T
        a = a.at[k0:k1, k0:k1].set(A11)
        if k1 < n:
            A21 = a[k1:, k0:k1]
            L21 = l[k1:, k0:k1]
            # A21 <- A21 L11^-H
            A21 = jax.lax.linalg.triangular_solve(
                L11, A21, left_side=False, lower=True,
                transpose_a=True, conjugate_a=True)
            half = jnp.asarray(0.5, a.dtype)
            corr = half * jnp.matmul(L21, A11, precision=HI)
            A21 = A21 - corr
            # her2k trailing update (the distributed bulk)
            upd = jnp.matmul(L21, jnp.conj(A21.T), precision=HI)
            a = constrain(
                a.at[k1:, k1:].add(-(upd + jnp.conj(upd.T))), grid)
            A21 = A21 - corr
            # A21 <- L22^-1 A21
            A21 = jax.lax.linalg.triangular_solve(
                l[k1:, k1:], A21, left_side=True, lower=True)
            a = a.at[k1:, k0:k1].set(A21)
    # the loop maintains the lower triangle; mirror for the dense out
    low = jnp.tril(a)
    return low + jnp.conj(jnp.tril(a, -1).T)


def hegst(itype: int, A: TiledMatrix, B: TiledMatrix,
          opts: OptionsLike = None) -> TiledMatrix:
    """Reduce generalized problem to standard form (reference
    src/hegst.cc, slate.hh:1199). B is the Cholesky factor from potrf.

    itype 1: A x = lambda B x   ->  C = L^-1 A L^-H
    itype 2/3: A B x = lambda x / B A x = lambda x -> C = L^H A L

    The itype=1 lower path runs the reference's BLOCKED two-sided
    transform (_hegst_blocked_lower) so the trailing updates
    distribute under a grid; upper and itype 2/3 use the whole-matrix
    form (matmul-rate single-device; reference hegst.cc specializes
    per uplo the same way)."""
    slate_assert(itype in (1, 2, 3), "hegst: itype in {1,2,3}")
    a = A.to_dense()
    rl = B.resolve()
    lower = rl.uplo is Uplo.Lower
    l = rl.to_dense()
    if itype == 1:
        if lower:
            grid = get_option(opts, Option.Grid, None)
            explicit_nb = int(get_option(opts, Option.BlockSize, 0))
            nb = explicit_nb or rl.nb
            # blocked form only where it buys something: under a grid
            # (the her2k updates shard; whole-matrix solves gather) or
            # on explicit request. Single-device default keeps the
            # two whole-matrix solves (matmul-rate, 2 dispatches).
            if a.shape[0] > nb and (grid is not None or explicit_nb):
                c = _hegst_blocked_lower(a, l, nb, grid)
            else:
                t = jax.lax.linalg.triangular_solve(
                    l, a, left_side=True, lower=True)
                c = jax.lax.linalg.triangular_solve(
                    l, t.conj().T, left_side=True,
                    lower=True).conj().T
        else:
            # B = U^H U: C = U^-H A U^-1
            t = jax.lax.linalg.triangular_solve(
                l, a, left_side=True, lower=False, transpose_a=True,
                conjugate_a=True)
            c = jax.lax.linalg.triangular_solve(
                l, t.conj().T, left_side=True, lower=False,
                transpose_a=True, conjugate_a=True).conj().T
    else:
        if lower:
            c = jnp.matmul(jnp.matmul(l.conj().T, a,
                                      precision=jax.lax.Precision.HIGHEST),
                           l, precision=jax.lax.Precision.HIGHEST)
        else:
            c = jnp.matmul(jnp.matmul(l, a,
                                      precision=jax.lax.Precision.HIGHEST),
                           l.conj().T,
                           precision=jax.lax.Precision.HIGHEST)
    out = _store(dataclasses.replace(A.resolve()), c)
    return dataclasses.replace(out, mtype=A.mtype)


@instrument_driver("hegv")
def hegv(itype: int, A: TiledMatrix, B: TiledMatrix,
         opts: OptionsLike = None, want_vectors: bool = True) -> EigResult:
    """Generalized Hermitian eigenproblem (reference src/hegv.cc,
    slate.hh:1143; sygv :1168): potrf(B), hegst, heev, back-transform."""
    L = potrf(B, opts)
    C = hegst(itype, A, L, opts)
    w, V = heev(C, opts, want_vectors)
    if not want_vectors:
        return EigResult(w, None)
    rl = L.resolve()
    lower = rl.uplo is Uplo.Lower
    l = rl.to_dense()
    v = V.to_dense()
    if itype == 1 or itype == 2:
        # x = L^-H y  (or U^-1 y)
        if lower:
            x = jax.lax.linalg.triangular_solve(
                l, v, left_side=True, lower=True, transpose_a=True,
                conjugate_a=True)
        else:
            x = jax.lax.linalg.triangular_solve(
                l, v, left_side=True, lower=False)
    else:
        # itype 3: x = L y (or U^H y)
        _hi = jax.lax.Precision.HIGHEST
        x = jnp.matmul(l, v, precision=_hi) if lower \
            else jnp.matmul(l.conj().T, v, precision=_hi)
    return EigResult(w, _store(V, x))


def sygv(itype: int, A: TiledMatrix, B: TiledMatrix,
         opts: OptionsLike = None, want_vectors: bool = True) -> EigResult:
    return hegv(itype, A, B, opts, want_vectors)


# -- two-stage pipeline entry points (parity surface) ---------------------

class TridiagResult(NamedTuple):
    d: jax.Array          # (n,) diagonal
    e: jax.Array          # (n-1,) off-diagonal
    Q: Optional[TiledMatrix]   # accumulated transform (if requested)


def _householder_tridiag(a: jax.Array, want_q: bool = True
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Householder tridiagonalization of dense Hermitian a, optionally
    accumulating Q; unrolled over columns (lapack sytrd contract)."""
    n = a.shape[0]
    q = jnp.eye(n if want_q else 1, dtype=a.dtype)
    rows = jnp.arange(n)

    def body(j, carry):
        a, q = carry
        x = jnp.where(rows > j, a[:, j], 0)
        v, tau, _ = _reflect(x, rows, j + 1)
        # two-sided update: A <- H A H,  H = I - tau v v^H
        w = tau * jnp.matmul(a, v,
                             precision=jax.lax.Precision.HIGHEST)
        k = 0.5 * tau * jnp.vdot(v, w)
        w = w - k * v
        a = a - jnp.outer(w, jnp.conj(v)) - jnp.outer(v, jnp.conj(w))
        if want_q:
            q = q - tau * jnp.outer(
                jnp.matmul(q, v, precision=jax.lax.Precision.HIGHEST),
                jnp.conj(v))
        return a, q

    a, q = jax.lax.fori_loop(0, n - 2, body, (a, q))
    d = jnp.real(jnp.diagonal(a))
    # diagonal phase similarity: the subdiagonal is complex for
    # Hermitian input (and possibly negative for real); D^H T D with
    # d_{k+1} = phase_k d_k makes it |e|, with Q scaled to match
    esub = jnp.diagonal(a, -1)
    mag = jnp.abs(esub)
    phase = jnp.where(mag == 0, 1.0,
                      esub / jnp.where(mag == 0, 1, mag)).astype(a.dtype)
    dphase = jnp.concatenate(
        [jnp.ones((1,), a.dtype), jnp.cumprod(phase)])
    e = mag.astype(d.dtype)
    if want_q:
        q = q * dphase[None, :]
    return d, e, (q if want_q else None)


#: panel count above which he2hb switches to the fixed-shape fori_loop
#: form (O(1) program size in nt; see blocked.CHOL_SCAN_THRESHOLD)
HE2HB_SCAN_THRESHOLD = 64


def _he2hb_scan(a: jax.Array, n: int, nb: int, want_q: bool):
    """he2hb's blocked step as ONE compiled body iterated by fori_loop
    (compile-time-safe form for huge nt). Roll discipline as in
    qr._geqrf_scan: the panel below the diagonal block is rolled to row
    0 and dead rows masked to exact zero, so every V/T/update matmul is
    full-size with zero contributions outside the live window and no
    per-step shape depends on k."""
    from .qr import _roll_live, _rolled_panel_factor
    HI = jax.lax.Precision.HIGHEST
    nt = ceil_div(max(n, 1), nb)
    rows = jnp.arange(n)
    q0 = jnp.eye(n if want_q else 1, dtype=a.dtype)

    def step(k, carry):
        a, q = carry
        k0 = k * nb
        k1 = k0 + nb
        live = n - k1
        colblk = jax.lax.dynamic_slice(a, (0, k0), (n, nb))
        packed, V, T, _ = _rolled_panel_factor(colblk, k1, live, rows)
        # write [R; 0] back into rows k1: of column block k0
        Rblk = jnp.zeros_like(packed).at[:nb].set(jnp.triu(packed[:nb]))
        Rblk = jnp.where((rows < live)[:, None], Rblk, 0)
        back = jnp.roll(Rblk, k1, axis=0)
        newblk = jnp.where((rows >= k1)[:, None], back, colblk)
        a = jax.lax.dynamic_update_slice(a, newblk, (0, k0))
        # two-sided compact-WY update of the trailing block, in the
        # doubly-rolled frame (dead rows of V kill wrapped rows/cols)
        Sr = _roll_live(jnp.roll(a, -k1, axis=1), k1, live, rows)
        P = jnp.matmul(Sr, V, precision=HI)
        W = jnp.matmul(P, T, precision=HI)
        Ssm = jnp.matmul(jnp.conj(T.T),
                         jnp.matmul(jnp.conj(V.T), W, precision=HI),
                         precision=HI)
        X = W - 0.5 * jnp.matmul(V, Ssm, precision=HI)
        dS = jnp.matmul(X, jnp.conj(V.T), precision=HI) \
            + jnp.matmul(V, jnp.conj(X.T), precision=HI)
        a = a - jnp.roll(jnp.roll(dS, k1, axis=0), k1, axis=1)
        if want_q:
            qc = jnp.roll(q, -k1, axis=1)
            dQ = jnp.matmul(
                jnp.matmul(jnp.matmul(qc, V, precision=HI), T,
                           precision=HI),
                jnp.conj(V.T), precision=HI)
            q = q - jnp.roll(dQ, k1, axis=1)
        return a, q

    return jax.lax.fori_loop(0, nt - 1, step, (a, q0))


def he2hb(A: TiledMatrix, opts: OptionsLike = None,
          want_q: bool = True):
    """Stage 1: full -> band of width nb (reference src/he2hb.cc,
    slate.hh:1229): blocked panel QR (native XLA geqrf where supported) +
    compact-WY two-sided trailing updates
    (A <- A - X V^H - V X^H with X = A V T - (1/2) V (T^H V^H A V T) —
    the reference's he2hb_hemm/her2k internal kernels as three large
    matmuls per panel). O(4 n^3 / 3) matmul FLOPs incl. the explicit Q
    accumulation, usable at n >= 8192 unlike the round-1 O(n)-step
    rank-2 loop. Returns (band_matrix, transform Q) with
    A = Q B Q^H."""
    from .qr import _larft, _panel_V, _qr_panel_blocked
    r = A.resolve()
    nb = r.mb
    n = r.n
    a = A.to_dense()
    nt = ceil_div(max(n, 1), nb)
    HI = jax.lax.Precision.HIGHEST
    if nt - 1 > HE2HB_SCAN_THRESHOLD:
        a, q = _he2hb_scan(a, n, nb, want_q)
        from ..core.matrix import HermitianBandMatrix
        B = HermitianBandMatrix(Uplo.Lower, min(nb, max(n - 1, 0)),
                                jnp.tril(a), mb=r.mb)
        Q = TiledMatrix.from_dense(q, r.mb, r.nb) if want_q else None
        return B, Q
    q = jnp.eye(n if want_q else 1, dtype=a.dtype)
    for k in range(nt - 1):
        k0, k1 = k * nb, min((k + 1) * nb, n)
        if n - k1 <= 0:
            break
        w = k1 - k0
        panel = a[k1:, k0:k1]
        packed, taus = _qr_panel_blocked(panel)
        V = _panel_V(packed, 0)                        # (n-k1, w)
        T = _larft(V, taus)
        R = jnp.triu(packed[:w])
        a = a.at[k1:, k0:k1].set(
            jnp.zeros_like(panel).at[:w].set(R))
        # two-sided compact-WY update of the trailing Hermitian block
        S = a[k1:, k1:]
        P = jnp.matmul(S, V, precision=HI)
        W = jnp.matmul(P, T, precision=HI)
        Ssm = jnp.matmul(jnp.conj(T.T),
                         jnp.matmul(jnp.conj(V.T), W, precision=HI),
                         precision=HI)
        X = W - 0.5 * jnp.matmul(V, Ssm, precision=HI)
        S = S - jnp.matmul(X, jnp.conj(V.T), precision=HI) \
            - jnp.matmul(V, jnp.conj(X.T), precision=HI)
        a = a.at[k1:, k1:].set(S)
        if want_q:
            # accumulate Q <- Q H (H = I - V T V^H acting on cols k1:)
            Qc = q[:, k1:]
            q = q.at[:, k1:].set(
                Qc - jnp.matmul(
                    jnp.matmul(jnp.matmul(Qc, V, precision=HI),
                               T, precision=HI),
                    jnp.conj(V.T), precision=HI))
    from ..core.matrix import HermitianBandMatrix
    B = HermitianBandMatrix(Uplo.Lower, min(nb, max(n - 1, 0)),
                            jnp.tril(a), mb=r.mb)
    Q = TiledMatrix.from_dense(q, r.mb, r.nb) if want_q else None
    return B, Q


#: n above which the staged stage-2 reductions (hb2st/tb2bd) warn on
#: TPU: their dense sequential fallbacks are O(n) dependent steps and
#: the measured crossover against just running the fused QDWH paths is
#: far below this (the dense fallback's n sequential reflections are
#: n dependent dispatches; the threshold is not measured on the
#: current machine)
STAGE2_TPU_WARN_N = 2048


def hb2st(B: TiledMatrix, opts: OptionsLike = None,
          want_q: bool = True) -> TridiagResult:
    """Stage 2: band -> tridiagonal (reference src/hb2st.cc bulge
    chasing — which the reference itself runs sequentially on a single
    node, heev.cc:117). Band width 1 is the identity extraction; wider
    bands reduce via the dense Householder loop below (O(n) dependent
    steps — the latency-bound stage on any hardware; the production
    eigensolver path is heev's QDWH eigh, which skips this entirely).
    Returns the tridiagonal plus this stage's own transform Q2: the
    full back-transform is unmtr_he2hb(Q_stage1, unmtr_hb2st(Q2, Z))
    like the reference's two-step apply (heev.cc:179-184)."""
    b = B.to_dense()
    kd = max(B.kl, B.ku)
    if kd <= 1:
        d = jnp.real(jnp.diagonal(b))
        e = jnp.real(jnp.diagonal(b, -1))
        return TridiagResult(d, e, None)
    r = B.resolve()
    from ..ops.pallas_kernels import _on_tpu
    if 2 <= kd <= r.n // 3 and not _on_tpu():
        # windowed block bulge chasing — O(n^2 kd) work instead of the
        # dense loop's O(n^3) (band.hb2st_band). CPU/host path only:
        # on TPU its n^2/kd tiny QR dispatches are pathologically
        # latency-bound (measured minutes at n=64), while the dense
        # loop's n vectorized steps stay tolerable — and the TPU
        # production eigensolver path is heev's QDWH anyway.
        from .band import hb2st_band
        d, e, q = hb2st_band(b, r.n, kd, want_q=want_q)
        return TridiagResult(
            d, e, TiledMatrix.from_dense(q, r.mb, r.nb)
            if want_q else None)
    if _on_tpu() and kd >= 2 and r.n > STAGE2_TPU_WARN_N:
        import warnings
        warnings.warn(
            "hb2st: on TPU the band->tridiagonal stage runs the dense "
            f"O(n^3) sequential fallback, impractical past n~"
            f"{STAGE2_TPU_WARN_N} (the windowed bulge chase is "
            "latency-bound there; PERF.md). The production TPU "
            "eigensolver is heev with MethodEig.Auto (fused QDWH), "
            "which skips stage 2 entirely.", stacklevel=2)
    d, e, q = _householder_tridiag(b, want_q=want_q)
    return TridiagResult(
        d, e, TiledMatrix.from_dense(q, r.mb, r.nb) if want_q else None)


def sterf(d: jax.Array, e: jax.Array, opts: OptionsLike = None):
    """Tridiagonal eigenvalues, no vectors (reference src/sterf.cc,
    slate.hh:1339): symmetric tridiagonal QR iteration. Delegates to the
    tridiagonal eigensolver."""
    return jnp.sort(
        jax.scipy.linalg.eigh_tridiagonal(d, e, eigvals_only=True))


def _steqr_shifted_sweep(d: jax.Array, e: jax.Array, ll, m, shift):
    """One shifted implicit symmetric-QR bulge-chase sweep on the
    active block [ll, m] of the tridiagonal (d, e) — the symmetric
    twin of svd._bdsqr_shifted_sweep (Golub & Van Loan alg. 8.3.2 /
    LAPACK dsteqr's rotation recurrence). Rotations outside the block
    are emitted as identity so one fixed-shape scan serves every
    deflation state. Verified identity: T' = G T G^T with G the
    composed chain of the returned (c, s)."""
    from .svd import _lartg
    n = d.shape[0]
    dt = d.dtype

    def body(carry, k):
        d, e, x, z = carry
        active = (k >= ll) & (k < m)
        x = jnp.where(k == ll, d[ll] - shift, x)
        z = jnp.where(k == ll, e[ll], z)
        c, s, r = _lartg(x, z, dt)
        km1 = jnp.maximum(k - 1, 0)
        e = e.at[km1].set(jnp.where(active & (k > ll), r, e[km1]))
        dk, dk1, ek = d[k], d[k + 1], e[k]
        d = d.at[k].set(jnp.where(
            active, c * c * dk + 2 * c * s * ek + s * s * dk1, dk))
        d = d.at[k + 1].set(jnp.where(
            active, s * s * dk - 2 * c * s * ek + c * c * dk1, dk1))
        enew = c * s * (dk1 - dk) + (c * c - s * s) * ek
        e = e.at[k].set(jnp.where(active, enew, ek))
        kp1 = jnp.minimum(k + 1, n - 2)
        z = jnp.where(active & (k < m - 1), s * e[kp1], z)
        e = e.at[kp1].set(jnp.where(active & (k < m - 1),
                                    c * e[kp1], e[kp1]))
        x = jnp.where(active, enew, x)
        one, zero = jnp.ones((), dt), jnp.zeros((), dt)
        return (d, e, x, z), (jnp.where(active, c, one),
                              jnp.where(active, s, zero))

    (d, e, _, _), (cs, sn) = jax.lax.scan(
        body, (d, e, jnp.zeros((), dt), jnp.zeros((), dt)),
        jnp.arange(n - 1))
    return d, e, cs, sn


def steqr2_qr(d: jax.Array, e: jax.Array,
              z0: Optional[jax.Array] = None, maxit_factor: int = 30):
    """Symmetric tridiagonal eigensolver by shifted implicit QR
    ITERATION — the literal algorithm of the reference's modified
    Fortran steqr2 (src/dsteqr2.f driven by src/steqr2.cc): per pass,
    negligible off-diagonals deflate to exact zero, the trailing
    active block [ll, m] is located, the Wilkinson shift comes from
    its trailing 2x2, and one gated bulge-chase sweep runs. Each
    sweep's rotation chain composes into ONE orthogonal matrix
    applied as a single matmul (svd._givens_chain_matrix — the
    transform-accumulation trick bdsqr_qr established), so vector
    accumulation is MXU work even though the d/e recurrence is
    sequential.

    z0: optional initial transform (rows, n) the sweeps accumulate
    onto — the identity by default. This is the dsteqr2.f slot: a
    caller may pass its back-transform Q directly (rows = n), or a
    ROW BLOCK of it (dist/steqr2.py shard_maps exactly that, making
    the accumulation row-local across the mesh with no communication).

    Returns (w, Z, info) ascending with Z = z0 @ (accumulated
    rotations), so for z0 = I, tridiag(d, e) = Z diag(w) Z^T; info
    counts off-diagonals still above tolerance at the iteration cap
    (LAPACK steqr INFO convention)."""
    from .svd import _givens_chain_matrix, _select_chain_apply
    n = d.shape[0]
    dt = d.dtype
    eps = jnp.finfo(dt).eps
    ids = jnp.arange(n - 1)

    def clamp(d, e):
        keep = jnp.abs(e) > eps * (jnp.abs(d[:-1]) + jnp.abs(d[1:]))
        return jnp.where(keep, e, 0.0)

    def cond(carry):
        d, e, Z, it = carry
        return jnp.any(clamp(d, e) != 0) & (it < maxit_factor * n)

    def body(carry):
        d, e, Z, it = carry
        e = clamp(d, e)
        nz = e != 0
        m = jnp.max(jnp.where(nz, ids, -1)) + 1     # block end (diag)
        ll = jnp.max(jnp.where((~nz) & (ids < m), ids, -1)) + 1
        # Wilkinson shift from the trailing 2x2 of the active block
        em1 = e[jnp.maximum(m - 1, 0)]
        delta = (d[jnp.maximum(m - 1, 0)] - d[m]) / 2
        sgn = jnp.where(delta >= 0, jnp.ones((), dt),
                        -jnp.ones((), dt))
        denom = jnp.abs(delta) + jnp.hypot(delta, em1)
        denom = jnp.where(denom == 0, jnp.ones((), dt), denom)
        shift = d[m] - sgn * em1 * em1 / denom
        d, e, cs, sn = _steqr_shifted_sweep(d, e, ll, m, shift)
        # _givens_chain_matrix returns the TRANSPOSE of the applied
        # chain R = R_{m-1}..R_ll (verified numerically): the sweep
        # computes T' = R T R^T = G^T T G, so T = G T' G^T and the
        # eigenvectors accumulate on the right as Z <- Z G. The
        # application route (dense compose vs the blocked Pallas
        # givens_chain_apply) is arbitrated once at trace time
        # (svd._select_chain_apply — op 'steqr2', cold default dense).
        if apply_chain is not None:
            Z = apply_chain(Z, cs, sn)
        else:
            G = _givens_chain_matrix(cs, sn, n, dt)
            Z = jnp.matmul(Z, G, precision=jax.lax.Precision.HIGHEST)
        return d, e, Z, it + 1

    if z0 is None:
        Zi = jnp.eye(n, dtype=dt)
    else:
        # promote once up front: the while_loop carry dtype must be
        # stable under Z @ G (G is in the tridiagonal's real dtype)
        Zi = jnp.asarray(z0)
        Zi = Zi.astype(jnp.promote_types(Zi.dtype, dt))
    apply_chain = _select_chain_apply("steqr2", Zi.shape[0], n, dt)
    d, e, Z, _ = jax.lax.while_loop(
        cond, body, (d, e, Zi, jnp.zeros((), jnp.int32)))
    info = jnp.sum(clamp(d, e) != 0).astype(jnp.int32)
    order = jnp.argsort(d)
    return d[order], Z[:, order], info


@instrument_driver("steqr2")
def steqr2(d: jax.Array, e: jax.Array, Q: Optional[TiledMatrix] = None,
           opts: OptionsLike = None, want_vectors: bool = True):
    """Distributed-slot tridiagonal QR iteration (reference
    src/steqr2.cc + modified Fortran dsteqr2.f, whose QR iteration
    updates only each rank's local eigenvector rows to bound per-rank
    memory and flops).

    The QR iteration now runs at EVERY n for real dtypes — the old
    STEQR_QR_MAX_N=512 reroute to stedc is gone. What removed it is
    the reference's own row-local play (dist/steqr2.py): under
    Option.Grid, Z's rows (or the caller's back-transform Q directly —
    the dsteqr2.f slot) shard over the mesh and every device
    accumulates the per-sweep composed rotation chain onto its own
    row block with zero communication, splitting the dominant
    accumulation cost P ways. Single-device keeps the same algorithm
    via z0 (one accumulation, no separate Q @ Z matmul). Complex
    dtypes still take stedc (the sweep recurrence is real); values-
    only requests use jax's O(n)-memory eigh_tridiagonal (sterf)."""
    if not want_vectors:
        slate_assert(Q is None,
                     "steqr2: want_vectors=False cannot apply Q")
        return sterf(d, e, opts), None
    if d.shape[0] <= 1 \
            or jnp.issubdtype(d.dtype, jnp.complexfloating):
        if d.shape[0] > 1:
            import warnings
            warnings.warn(
                "steqr2: dtype %s is complex; the divide & conquer "
                "solver (stedc) runs instead. Spectra match; "
                "deflation tolerances differ in ulps." % d.dtype,
                stacklevel=2)
        return stedc(d, e, Q, opts)
    grid = get_option(opts, Option.Grid, None)
    z0 = Q.to_dense() if Q is not None else None
    if grid is not None:
        from ..dist.steqr2 import steqr2_qr_dist
        w, Z, _info = steqr2_qr_dist(grid, d, e, z0=z0)
    else:
        if d.shape[0] > 2048:
            import warnings
            warnings.warn(
                "steqr2: n=%d single-device QR iteration accumulates "
                "~2n^3 flops PER SWEEP over O(n) sweeps (PERF.md "
                "Round-6 cost note). It runs as requested — pass "
                "Option.Grid to split the accumulation across a mesh "
                "(dist/steqr2.py), or use stedc for the O(n^3) D&C."
                % d.shape[0], stacklevel=2)
        w, Z, _info = steqr2_qr(d, e, z0=z0)
    if Q is not None:
        return w, _store(Q, Z)
    return w, Z


@instrument_driver("stedc")
def stedc(d: jax.Array, e: jax.Array, Q: Optional[TiledMatrix] = None,
          opts: OptionsLike = None):
    """Divide & conquer tridiagonal eigensolver (reference src/stedc.cc
    + stedc_{deflate,merge,secular,solve,sort,z_vector}.cc) — Cuppen
    rank-one merging with vectorized secular bisection; see
    linalg/stedc.py for the phase mapping. Under Option.Grid the
    distributed driver runs instead (dist/stedc.py: leaves batched
    across devices, eigenvector workspace sharded, top-level merge
    matmuls SPMD-partitioned — the reference's rank-parallel stedc,
    stedc_solve.cc:97-171), and the Q back-transform matmul is
    constrained over the mesh. The leaf size is a tunable
    ('stedc'/'leaf'; frozen default 32)."""
    from ..parallel.sharding import constrain
    from ..tune.select import tuned_int
    from .stedc import stedc_solve
    d = jnp.asarray(d)
    leaf = tuned_int("stedc", "leaf", 32, opts=opts, n=d.shape[0],
                     dtype=d.dtype)
    grid = get_option(opts, Option.Grid, None)
    if grid is not None and d.shape[0] > leaf:
        from ..dist.stedc import matmul_sharded, stedc_solve_dist
        w, v = stedc_solve_dist(grid, d, e, leaf=leaf)
        if Q is not None:
            # back-transform through the explicit shard_map matmul —
            # a plain sharding constraint on this product back-
            # propagates into the merge scans and miscompiles them
            # (dist/stedc.py module doc)
            from jax.sharding import PartitionSpec as _P
            v = constrain(v, grid, _P())
            q = matmul_sharded(grid, Q.to_dense(), v.astype(Q.dtype))
            return w, _store(Q, q)
        return w, v
    w, v = stedc_solve(d, e, leaf=leaf)
    if Q is not None:
        q = constrain(Q.to_dense() @ v.astype(Q.dtype), grid)
        return w, _store(Q, q)
    return w, v


# -- back-transforms (reference slate.hh:1237-1330) ----------------------

def unmtr_he2hb(Q: TiledMatrix, C: TiledMatrix,
                opts: OptionsLike = None) -> TiledMatrix:
    """Apply the stage-1 (full->band) transform to C (reference
    src/unmtr_he2hb.cc, slate.hh:1237). he2hb returns the accumulated Q
    explicitly, so the back-transform is one distributed matmul."""
    import jax.numpy as _jnp
    q = Q.to_dense()
    c = C.to_dense()
    return _store(C, _jnp.matmul(q, c,
                                 precision=jax.lax.Precision.HIGHEST))


def unmtr_hb2st(V: TiledMatrix, C: TiledMatrix,
                opts: OptionsLike = None) -> TiledMatrix:
    """Apply the stage-2 (band->tridiagonal) transform (reference
    src/unmtr_hb2st.cc, slate.hh:1255)."""
    return unmtr_he2hb(V, C, opts)
