"""SVD (reference src/svd.cc, ge2tb.cc, tb2bd.cc, bdsqr.cc,
unmbr_ge2tb.cc, unmbr_tb2bd.cc; SURVEY §3.5).

TPU-native design. The reference pipeline is ge2tb (dense -> triangular
band) -> tb2bd (band -> bidiagonal wavefront bulge chase) -> bdsqr
(bidiagonal QR iteration on 1D-distributed U/VT rows) -> two
back-transforms. The production `svd` path is a QDWH-SVD — polar
decomposition + Hermitian eig, all MXU matmuls — because the bulge
chase's tiny sequential dispatches are the anti-pattern on TPU.

On the chip, for a concrete real f32 square matrix above the routing
threshold `st.heev` uses (`spectral_dc.route`), that QDWH-SVD is this
library's own (`_svd_qdwh_dc`, PR 39): A = U_p H with U_p from the
eigensolver's polar program at the root's bucket (`spectral_dc.dc_sign`
serves a general matrix under a traced flag: ONE executable a bucket
for both drivers), H = sym(U_p^T A) = V diag(s) V^T by the
eigensolver's host agenda over per-bucket programs
(`spectral_dc.eigh_dc`), U = U_p V. The SVD adds two small programs of
its own (the form and the compose) and compiles nothing heavy:
every heavy program is one `st.heev` compiled. Like `heev` on that
route it returns once the host has read the last split's sizes (and
the polar's flags), not at once. Everywhere else (a caller's jit,
rectangular, complex, f64 or smaller input, another backend) `svd` is
XLA's fused QDWH-SVD, `jax.lax.linalg.svd`: one program holding a
polar iteration and a whole spectral divide and conquer, which at
n=8192 is 1.4 GB of code, 10 GB of temporaries and half an hour of
compile that no cache holds (PERF.md, PR 39).

The staged names are REAL algorithms, not aliases: ge2tb is a blocked
two-sided QR/LQ reduction (fused Pallas panels, fixed-shape scan form
at huge nt), tb2bd runs the windowed bulge chase (band.tb2bd_band) on
the CPU/host path, and bdsqr runs the shifted implicit-QR iteration
with deflation (bdsqr_qr) there — each with the TPU fallback
documented at its definition.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.enums import MatrixType, Uplo
from ..core.options import OptionsLike
from ..core.tiles import TiledMatrix
from ..obs.events import instrument_driver
from .blas3 import _store
from ..ops.householder import reflect as _reflect


class SVDResult(NamedTuple):
    s: jax.Array                       # (min(m,n),) descending
    U: Optional[TiledMatrix]
    Vh: Optional[TiledMatrix]


@jax.jit
def _svd_form(up, a):
    """H = sym(U_p^T A): the Hermitian polar factor of A, whose
    eigenpairs are A's singular values and right singular vectors.
    One product at the full size. Nothing is donated: `a` is the
    caller's matrix and the compose needs U_p."""
    h = jnp.matmul(up.T, a, precision=jax.lax.Precision.HIGHEST)
    return 0.5 * (h + h.T)


def _svd_compose(w, v, up, want_vh: bool = True):
    """(s, U, Vh) out of H's eigenpairs (w ascending, V) and the polar
    factor: s = w descending, clipped at 0 (H is positive semidefinite
    up to rounding); U = U_p V and Vh = V^T with their columns (rows)
    reversed to match. `v` None: no factor is wanted; `up` None: no U."""
    s = jnp.maximum(w[::-1], jnp.zeros((), w.dtype))
    if v is None:
        return s, None, None
    vr = v[:, ::-1]
    u = None if up is None else jnp.matmul(
        up, vr, precision=jax.lax.Precision.HIGHEST)
    return s, u, (vr.T if want_vh else None)


#: the compose as a program: with both factors wanted (the usual call)
#: both workspaces are donated and U, Vh take their buffers
_svd_compose_both = jax.jit(_svd_compose, donate_argnums=(1, 2))
_svd_compose_part = jax.jit(_svd_compose, static_argnames=("want_vh",))


def _svd_qdwh_dc(A: TiledMatrix, a, leaf: int, span, want_u: bool,
                 want_vh: bool) -> SVDResult:
    """`svd`'s own route on the chip (module doc): A = U_p H by the
    eigensolver's polar program, H = V diag(s) V^T by its agenda,
    U = U_p V."""
    import warnings
    import numpy as np
    from ..obs import metrics as obs_metrics
    from . import spectral_dc
    with span("svd::polar"):
        up, flags = spectral_dc.polar_general(a, leaf)
    with span("svd::form"):
        h = _svd_form(up, a)
    with span("svd::eig"):
        w, v, ok = spectral_dc.eigh_dc(h, leaf=leaf)
    with span("svd::compose"):
        if want_u and want_vh:
            s, u, vh = _svd_compose_both(w, v, up)
        else:
            s, u, vh = _svd_compose_part(
                w, v if (want_u or want_vh) else None,
                up if want_u else None, want_vh=want_vh)
    # the polar's flags were computed before the eigensolver's root
    # split ran, and everything after them is dispatched by now: the
    # device does not wait for this read
    with span("svd::agenda"):
        _, conv, iters, _ = (int(x) for x in np.asarray(flags))
    obs_metrics.inc("svd.polar_iters", iters)
    if not conv:
        obs_metrics.inc("svd.unconverged")
    if not (conv and ok):
        warnings.warn(
            "svd: the polar iteration of %s hit its iteration cap "
            "without converging; singular triplets may be degraded "
            "(polar.py capped-weight schedule)"
            % ("A" if not conv else "a split of A's Hermitian factor"),
            stacklevel=3)
    r = A.resolve()
    return SVDResult(
        s, None if u is None else TiledMatrix.from_dense(u, r.mb, r.nb),
        None if vh is None else TiledMatrix.from_dense(vh, r.mb, r.nb))


def agenda_leaf(a, opts=None):
    """The leaf size at which `svd` with no method set takes its own
    route on `a` (an array, or anything with its shape and dtype), or
    None where it is jax's fused program: the route needs a concrete
    real f32 square matrix (the shared polar program is the
    eigensolver's, and its cache entries are f32's) of more rows than
    `heev`'s threshold and than a leaf (`spectral_dc.route`)."""
    from . import spectral_dc
    if len(a.shape) != 2 or a.shape[0] != a.shape[1] \
            or a.dtype != jnp.float32:
        return None
    leaf = spectral_dc.route(a, opts)
    return leaf if leaf is not None and a.shape[0] > leaf else None


@instrument_driver("svd")
def svd(A: TiledMatrix, opts: OptionsLike = None,
        want_u: bool = True, want_vh: bool = True) -> SVDResult:
    """Singular value decomposition (reference src/svd.cc, slate.hh:997;
    gesvd alias).

    Option.MethodSVD routes the solve (reference svd.cc:216-322, one
    routed driver), mirroring heev's MethodEig routing:
    - Auto: a QDWH-SVD, polar decomposition + Hermitian eig, all MXU
      matmuls. On the chip, for a concrete real square matrix of more
      rows than `heev`'s own threshold (`spectral_dc.route`: 2048, the
      one tune entry both drivers read), it is this library's:
      A = U_p H by the eigensolver's polar program at the root's
      bucket, H = V diag(s) V^T by its host agenda
      (`spectral_dc.eigh_dc`), U = U_p V; it returns once the last
      split's sizes and the polar's flags are read, not at once.
      Everything else (a caller's jit, a complex, rectangular or
      smaller matrix, another backend without a tune entry written
      there) is jax's fused one-program `jax.lax.linalg.svd`.
    - QRIteration: the staged reference pipeline ge2tb -> tb2bd ->
      bdsqr with both back-transforms composed (each stage's TPU/host
      split documented at its definition).
    - DC: the same route as Auto: a QDWH-SVD IS a divide & conquer
      (polar split + D&C Hermitian eig), so the reference's DC slot
      maps to it.

    `want_u` / `want_vh` false skip their products of the compose
    (`svd_vals` takes the same route: the polar and the eigensolver
    are what give s, and its programs are the ones already compiled).

    Spans `svd::prep`, `svd::polar`, `svd::form`, `svd::eig` (the
    `heev::*` spans and `heev.*` counters open inside it),
    `svd::compose`, `svd::agenda` (the host's read of the polar's
    flags); the root span carries the route (`method` `qdwh_dc` or
    `xla_svd`, `form` `agenda` or `native`, `leaf`, `buckets`);
    counters `svd.solves`, `svd.polar_iters`, `svd.unconverged`."""
    from ..core.methods import MethodSVD
    from ..core.options import Option, get_option
    method = get_option(opts, Option.MethodSVD, MethodSVD.Auto)
    if method is MethodSVD.Auto:
        # measured Auto routing from the tune cache (mirrors heev's
        # MethodEig); cold cache keeps the default below
        from ..tune.select import tuned_method
        cached = tuned_method("svd", "svd", opts=opts,
                              option=Option.MethodSVD,
                              n=min(A.shape), dtype=A.dtype)
        if cached is not None and cached is not MethodSVD.Auto:
            method = cached
    if method is MethodSVD.QRIteration:
        from ..ops.pallas_kernels import _on_tpu
        if _on_tpu():
            import warnings
            warnings.warn(
                "svd: MethodSVD.QRIteration runs the staged pipeline, "
                "but on TPU its bdsqr stage solves the bidiagonal with "
                "the fused XLA SVD, not rotation-chain QR iteration "
                "(that path is gated to host/CPU at n<=%d; see bdsqr). "
                "Singular values match." % BDSQR_QR_MAX_N, stacklevel=2)
        Bd = tb2bd(ge2tb(A, opts), opts)
        if not (want_u or want_vh):
            # skip the O(n^3) back-transform composition in bdsqr for
            # a values-only request (the reduction stages still
            # accumulate their transforms — the staged contract)
            Bd = Bd._replace(U=None, Vh=None)
        res = bdsqr(Bd, opts)
        return SVDResult(res.s, res.U if want_u else None,
                         res.Vh if want_vh else None)
    from ..obs import metrics as obs_metrics
    from ..obs.events import note
    from ..utils.trace import phases
    from . import spectral_dc
    # named `span` so that the lint's registry of published names
    # (docs/OBS_REFERENCE.md) reads these sites
    span = phases(opts)
    obs_metrics.inc("svd.solves")
    with span("svd::prep"):
        a = A.to_dense()
        leaf = agenda_leaf(a, opts)
    if leaf is not None:
        note(method="qdwh_dc", **spectral_dc.route_note(a.shape[0], leaf))
        return _svd_qdwh_dc(A, a, leaf, span, want_u, want_vh)
    note(method="xla_svd", form="native")
    if want_u or want_vh:
        u, s, vh = jax.lax.linalg.svd(a, full_matrices=False)
        r = A.resolve()
        U = TiledMatrix.from_dense(u, r.mb, r.nb) if want_u else None
        Vh = TiledMatrix.from_dense(vh, r.mb, r.nb) if want_vh else None
        return SVDResult(s, U, Vh)
    s = jax.lax.linalg.svd(a, compute_uv=False)
    return SVDResult(s, None, None)


def svd_vals(A: TiledMatrix, opts: OptionsLike = None) -> jax.Array:
    """Reference slate.hh:997 svd_vals."""
    return svd(A, opts, want_u=False, want_vh=False).s


def gesvd(A: TiledMatrix, opts: OptionsLike = None, **kw) -> SVDResult:
    return svd(A, opts, **kw)


# -- staged pipeline entry points (parity surface) ------------------------

class BidiagResult(NamedTuple):
    d: jax.Array          # (k,) diagonal
    e: jax.Array          # (k-1,) superdiagonal
    U: Optional[TiledMatrix]
    Vh: Optional[TiledMatrix]


def _stage2_warn_n() -> int:
    """Shared TPU stage-2 size threshold (eig.STAGE2_TPU_WARN_N)."""
    from .eig import STAGE2_TPU_WARN_N
    return STAGE2_TPU_WARN_N


def _golub_kahan(a: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array,
                                        jax.Array]:
    """Golub-Kahan bidiagonalization with accumulated U, V^H (lapack
    gebrd contract, upper bidiagonal). After the loop
    A = U B Vh with B = (prod_j H_j) A (prod_j G_j):
    left step  A <- H A,  H = I - tau v v^H,  U <- U H^H;
    right step A <- A G,  G = I - conj(taur) vr vr^H (vr built from the
    conjugated row), Vh <- G^H Vh."""
    m, n = a.shape
    u = jnp.eye(m, dtype=a.dtype)
    vh = jnp.eye(n, dtype=a.dtype)
    rowsm = jnp.arange(m)
    rowsn = jnp.arange(n)

    def body(j, carry):
        a, u, vh = carry
        # left reflector: zero column j below the diagonal
        x = jnp.where(rowsm >= j, a[:, j], 0)
        v, tau, _ = _reflect(x, rowsm, j)
        w = tau * jnp.matmul(jnp.conj(v), a,
                             precision=jax.lax.Precision.HIGHEST)
        a = a - jnp.outer(v, w)
        u = u - jnp.conj(tau) * jnp.outer(
            jnp.matmul(u, v, precision=jax.lax.Precision.HIGHEST),
            jnp.conj(v))
        # right reflector: zero row j beyond the superdiagonal
        y = jnp.where(rowsn >= j + 1, jnp.conj(a[j]), 0)
        vr, taur, _ = _reflect(y, rowsn, j + 1)
        aw = jnp.matmul(a, vr,
                        precision=jax.lax.Precision.HIGHEST)
        a = a - jnp.conj(taur) * jnp.outer(aw, jnp.conj(vr))
        vh = vh - taur * jnp.outer(
            vr, jnp.matmul(jnp.conj(vr), vh,
                           precision=jax.lax.Precision.HIGHEST))
        return a, u, vh

    k = min(m, n)
    a, u, vh = jax.lax.fori_loop(0, k, body, (a, u, vh))
    d = jnp.diagonal(a)[:k]
    e = jnp.diagonal(a, 1)[:max(k - 1, 0)]
    return d, e, u, vh


class Ge2tbResult(NamedTuple):
    """Stage-1 output: upper triangular band B of width nb with
    A = U B Vh (transforms accumulated explicitly)."""
    B: TiledMatrix
    U: TiledMatrix
    Vh: TiledMatrix


#: panel count above which ge2tb switches to the fixed-shape fori_loop
#: form (O(1) program size in nt; see blocked.CHOL_SCAN_THRESHOLD)
GE2TB_SCAN_THRESHOLD = 64


def _ge2tb_scan(a: jax.Array, m: int, n: int, nb: int):
    """ge2tb's alternating QR/LQ panel step as ONE compiled body
    iterated by fori_loop (compile-time-safe form for huge nt, m >= n).
    Roll discipline as in qr._geqrf_scan: panels roll their diagonal to
    index 0 with dead rows masked to exact zero, so every update matmul
    is full-size and contributes exact zeros outside the live window.

    `a` is the TILE-PADDED dense (Mp, Np) — fixed-size panel slices
    need whole blocks; live masks use the logical m, n so pad rows/cols
    contribute exact zeros and U/Vh pad lanes stay identity (cropped by
    the caller)."""
    from ..core.tiles import ceil_div
    from .qr import _roll_live, _rolled_panel_factor
    HI = jax.lax.Precision.HIGHEST
    Mp, Np = a.shape
    nt = ceil_div(max(min(m, n), 1), nb)
    rowsm = jnp.arange(Mp)
    rowsn = jnp.arange(Np)
    u0 = jnp.eye(Mp, dtype=a.dtype)
    vh0 = jnp.eye(Np, dtype=a.dtype)

    def step(k, carry):
        a, u, vh = carry
        k0 = k * nb
        k1 = k0 + nb
        livem = m - k0
        liven = n - k1
        # -- left QR panel on column block k0, rolled to row 0
        colblk = jax.lax.dynamic_slice(a, (0, k0), (Mp, nb))
        packed, V, T, _ = _rolled_panel_factor(colblk, k0, livem, rowsm)
        Rblk = jnp.zeros_like(packed).at[:nb].set(jnp.triu(packed[:nb]))
        Rblk = jnp.where((rowsm < livem)[:, None], Rblk, 0)
        back = jnp.roll(Rblk, k0, axis=0)
        newblk = jnp.where((rowsm >= k0)[:, None], back, colblk)
        a = jax.lax.dynamic_update_slice(a, newblk, (0, k0))
        # trailing update Q^H C on columns >= k1 (rows rolled by k0)
        ar = _roll_live(a, k0, livem, rowsm)
        Cm = jnp.where((rowsn >= k1)[None, :], ar, 0)
        Wm = jnp.matmul(jnp.conj(T.T),
                        jnp.matmul(jnp.conj(V.T), Cm, precision=HI),
                        precision=HI)
        a = a - jnp.roll(jnp.matmul(V, Wm, precision=HI), k0, axis=0)
        # U accumulation on columns >= k0 (columns rolled by k0)
        uc = jnp.roll(u, -k0, axis=1)
        dU = jnp.matmul(
            jnp.matmul(jnp.matmul(uc, V, precision=HI), T, precision=HI),
            jnp.conj(V.T), precision=HI)
        u = u - jnp.roll(dU, k0, axis=1)
        # -- right LQ panel on row block k0, columns >= k1
        rowblk = jax.lax.dynamic_slice(a, (k0, 0), (nb, Np))
        d = jnp.conj(rowblk.T)                          # (Np, nb)
        packed2, V2, T2, _ = _rolled_panel_factor(d, k1, liven, rowsn)
        # write [L 0] into columns >= k1 of the row block
        Lblk = jnp.zeros_like(packed2).at[:nb].set(
            jnp.triu(packed2[:nb]))
        Lblk = jnp.where((rowsn < liven)[:, None], Lblk, 0)
        Lrow = jnp.conj(jnp.roll(Lblk, k1, axis=0).T)   # (nb, n)
        newrow = jnp.where((rowsn >= k1)[None, :], Lrow, rowblk)
        a = jax.lax.dynamic_update_slice(a, newrow, (k0, 0))
        # trailing update C G on rows >= k1 (columns rolled by k1)
        ac = jnp.roll(a, -k1, axis=1)
        ac = jnp.where((rowsm >= k1)[:, None], ac, 0)
        P2 = jnp.matmul(ac, V2, precision=HI)
        dC = jnp.matmul(jnp.matmul(P2, T2, precision=HI),
                        jnp.conj(V2.T), precision=HI)
        a = a - jnp.roll(dC, k1, axis=1)
        # Vh accumulation on rows >= k1 (rows rolled by k1)
        vr = jnp.roll(vh, -k1, axis=0)
        dV = jnp.matmul(
            jnp.matmul(V2, jnp.conj(T2.T), precision=HI),
            jnp.matmul(jnp.conj(V2.T), vr, precision=HI),
            precision=HI)
        vh = vh - jnp.roll(dV, k1, axis=0)
        return a, u, vh

    return jax.lax.fori_loop(0, nt, step, (a, u0, vh0))


def ge2tb(A: TiledMatrix, opts: OptionsLike = None) -> Ge2tbResult:
    """Stage 1: dense -> upper triangular band of width nb (reference
    src/ge2tb.cc, slate.hh:1062): alternating blocked QR column panels
    and LQ row panels (native XLA geqrf where supported) with compact-WY
    trailing updates — all bulk work large matmuls, usable at
    n >= 8192 unlike the round-1 O(n)-step Golub-Kahan loop."""
    from .qr import _larft, _panel_V, _qr_panel_blocked
    HI = jax.lax.Precision.HIGHEST
    r = A.resolve()
    nb = r.nb
    m, n = r.m, r.n
    kmax = min(m, n)
    from ..core.tiles import ceil_div
    nt = ceil_div(max(kmax, 1), nb)
    ap = r.data                      # tile-padded dense
    if nt > GE2TB_SCAN_THRESHOLD and m >= n \
            and min(ap.shape) >= nt * nb:
        # tall/square only (like qr._geqrf_scan): every column block
        # gets panel-factored, so fixed-width panels are safe. Runs
        # before the unrolled path's dense/eye materialization, which
        # would waste O(m^2) HBM exactly in the huge-nt regime.
        apad, up, vhp = _ge2tb_scan(ap, m, n, nb)
        ku = min(nb, max(n - 1, 0))
        B = dataclasses.replace(
            TiledMatrix.from_dense(apad[:m, :n], r.mb, r.nb),
            mtype=MatrixType.GeneralBand, kl=0, ku=ku)
        return Ge2tbResult(B,
                           TiledMatrix.from_dense(up[:m, :m], r.mb,
                                                  r.mb),
                           TiledMatrix.from_dense(vhp[:n, :n], r.nb,
                                                  r.nb))
    a = A.to_dense()
    u = jnp.eye(m, dtype=a.dtype)
    vh = jnp.eye(n, dtype=a.dtype)
    for k in range(nt):
        k0, k1 = k * nb, min((k + 1) * nb, kmax)
        w = k1 - k0
        # left QR panel: zero column block below the diagonal
        packed, taus = _qr_panel_blocked(a[k0:, k0:k1])
        V = _panel_V(packed, 0)
        T = _larft(V, taus)
        R = jnp.triu(packed[:w])
        a = a.at[k0:, k0:k1].set(
            jnp.zeros_like(a[k0:, k0:k1]).at[:w].set(R))
        if k1 < n:
            C = a[k0:, k1:]
            Wm = jnp.matmul(
                jnp.conj(T.T),
                jnp.matmul(jnp.conj(V.T), C, precision=HI),
                precision=HI)
            a = a.at[k0:, k1:].set(
                C - jnp.matmul(V, Wm, precision=HI))
        Uc = u[:, k0:]
        u = u.at[:, k0:].set(
            Uc - jnp.matmul(
                jnp.matmul(jnp.matmul(Uc, V, precision=HI), T,
                           precision=HI),
                jnp.conj(V.T), precision=HI))
        # right LQ panel: zero row block beyond the nb band
        if k1 < n:
            rowblk = a[k0:k1, k1:]                    # (w, n-k1)
            d = jnp.conj(rowblk.T)                    # (n-k1, w)
            packed2, taus2 = _qr_panel_blocked(d)
            V2 = _panel_V(packed2, 0)
            T2 = _larft(V2, taus2)
            L = jnp.conj(jnp.triu(packed2[:w]).T)     # (w, w) lower
            newrow = jnp.zeros_like(rowblk)
            newrow = newrow.at[:, :w].set(L)
            a = a.at[k0:k1, k1:].set(newrow)
            if k1 < m:
                C = a[k1:, k1:]
                # A <- A G, G = I - V2 T2 V2^H
                CV = jnp.matmul(C, V2, precision=HI)
                a = a.at[k1:, k1:].set(
                    C - jnp.matmul(jnp.matmul(CV, T2, precision=HI),
                                   jnp.conj(V2.T), precision=HI))
            # Vh <- G^H Vh on rows k1:
            Vr = vh[k1:, :]
            vh = vh.at[k1:, :].set(
                Vr - jnp.matmul(
                    jnp.matmul(V2, jnp.conj(T2.T), precision=HI),
                    jnp.matmul(jnp.conj(V2.T), Vr, precision=HI),
                    precision=HI))
    ku = min(nb, max(n - 1, 0))
    B = dataclasses.replace(TiledMatrix.from_dense(a, r.mb, r.nb),
                            mtype=MatrixType.GeneralBand, kl=0, ku=ku)
    return Ge2tbResult(B,
                       TiledMatrix.from_dense(u, r.mb, r.mb),
                       TiledMatrix.from_dense(vh, r.nb, r.nb))


def tb2bd(F, opts: OptionsLike = None) -> BidiagResult:
    """Stage 2: band -> bidiagonal (reference src/tb2bd.cc wavefront
    bulge chase — sequential on any hardware; the reference runs it on
    gathered band data too, svd.cc:227). Genuinely banded input takes
    the windowed bulge chase (band.tb2bd_band, O(n^2 kd) work) on the
    CPU/host path; on TPU its n^2/kd tiny QR dispatches are
    pathologically latency-bound (same measurement as hb2st,
    eig.py), so the dense Golub-Kahan fallback runs there — and the
    TPU production SVD path is svd's QDWH, which skips stage 2
    entirely. Accepts a BidiagResult passthrough for already-
    bidiagonal input."""
    if isinstance(F, BidiagResult):
        return F
    r = F.B.resolve()
    n = min(r.m, r.n)
    kd = r.ku if r.ku >= 0 else 0
    b = F.B.to_dense()
    HI = jax.lax.Precision.HIGHEST
    from ..ops.pallas_kernels import _on_tpu
    # kl <= 0 required: tb2bd_band assumes a purely UPPER band (ge2tb
    # always produces one, but tb2bd accepts any Ge2tbResult)
    if 2 <= kd <= n // 3 and r.m == r.n and r.kl <= 0 \
            and not _on_tpu():
        from .band import tb2bd_band
        d, e, u2, vh2 = tb2bd_band(b, n, kd, want_uv=True)
    else:
        if _on_tpu() and kd >= 2 and n > _stage2_warn_n():
            import warnings
            warnings.warn(
                "tb2bd: on TPU the band->bidiagonal stage runs the "
                "dense O(n^3) sequential fallback, impractical past "
                f"n~{_stage2_warn_n()} (eig.STAGE2_TPU_WARN_N). The "
                "production TPU SVD is svd with MethodSVD.Auto "
                "(fused QDWH), which skips stage 2 entirely.",
                stacklevel=2)
        d, e, u2, vh2 = _golub_kahan(b)
    u = jnp.matmul(F.U.to_dense(), u2, precision=HI)
    vh = jnp.matmul(vh2, F.Vh.to_dense(), precision=HI)
    return BidiagResult(d, e,
                        TiledMatrix.from_dense(u, F.U.mb, F.U.nb),
                        TiledMatrix.from_dense(vh, F.Vh.mb, F.Vh.nb))


def _givens_chain_matrix(cs: jax.Array, sn: jax.Array, n: int, dtype
                         ) -> jax.Array:
    """Compose the chained Givens rotations G_0 ... G_{n-2} (G_k acts
    on index pair (k, k+1): out_k = c x_k + s x_{k+1},
    out_{k+1} = -s x_k + c x_{k+1}) into ONE (n, n) orthogonal matrix.
    Index k is finalized at step k (later rotations never touch it),
    so a scan with a single n-vector of coefficients builds the matrix
    — the same one-matmul application trick as
    stedc.stedc_rotation_matrix."""
    eye = jnp.eye(n, dtype=dtype)
    ids = jnp.arange(n)

    def step(alpha, k):
        c, s = cs[k], sn[k]
        e_next = (ids == k + 1).astype(dtype)
        col = c * alpha + s * e_next
        return -s * alpha + c * e_next, col

    alpha, cols = jax.lax.scan(step, eye[:, 0], jnp.arange(n - 1))
    return jnp.concatenate([cols.T, alpha[:, None]], axis=1)


def _select_chain_apply(op: str, rows: int, n: int, dt):
    """Pick the sweep-chain application route ONCE at trace time for
    a QR-iteration driver (steqr2_qr / bdsqr_qr): a blocked applier
    with apply(Z, cs, sn) == Z @ _givens_chain_matrix(cs, sn, n, dt),
    or None meaning KEEP the dense compose — the caller's unchanged
    (and bit-identical) cold path.

    Arbitration (ISSUE 6): a MEASURED tune-cache entry ((op, 'chain')
    == 'pallas_rec') routes to the blocked Pallas kernel
    (ops/pallas_kernels.givens_chain_apply — banded (2b, 2b) block
    factors applied as MXU matmuls, O(n^2 b) per sweep instead of the
    dense compose's O(n^3)) when its eligibility gate accepts; the
    frozen default is 'dense', so an empty cache never reroutes."""
    from ..ops import pallas_kernels as pk
    from ..tune.select import resolve
    route = resolve(op, "chain", n=n, dtype=dt, fallback="dense")
    if str(route) != "pallas_rec" \
            or not pk.givens_chain_eligible(rows, n, dt):
        return None

    def apply_blocked(Z, cs, sn):
        out = pk.givens_chain_apply(Z, cs, sn)
        if out is None:        # gate accepted but dispatch declined
            return jnp.matmul(Z, _givens_chain_matrix(cs, sn, n, dt),
                              precision=jax.lax.Precision.HIGHEST)
        return out

    return apply_blocked


def _lartg(f, g, dt):
    """Plane rotation (c, s, r) with c f + s g = r (LAPACK dlartg)."""
    r = jnp.hypot(f, g)
    safe = jnp.where(r == 0, jnp.ones((), dt), r)
    c = jnp.where(r == 0, jnp.ones((), dt), f / safe)
    s = jnp.where(r == 0, jnp.zeros((), dt), g / safe)
    return c, s, r


def _dlas2_min(f, g, h):
    """Smallest singular value of [[f, g], [0, h]] (LAPACK dlas2)."""
    fa, ga, ha = jnp.abs(f), jnp.abs(g), jnp.abs(h)
    fhmn = jnp.minimum(fa, ha)
    fhmx = jnp.maximum(fa, ha)
    fhmx_s = jnp.where(fhmx == 0, 1.0, fhmx)
    ga_s = jnp.where(ga == 0, 1.0, ga)
    as_ = 1.0 + fhmn / fhmx_s
    at = (fhmx - fhmn) / fhmx_s
    au1 = (ga / fhmx_s) ** 2
    c1 = 2.0 / (jnp.sqrt(as_ * as_ + au1) + jnp.sqrt(at * at + au1))
    au2 = fhmx / ga_s
    c2 = 1.0 / (jnp.sqrt(1.0 + (as_ * au2) ** 2)
                + jnp.sqrt(1.0 + (at * au2) ** 2))
    ssmin_big_g = jnp.where(au2 == 0, fhmn * fhmx / ga_s,
                            2.0 * fhmn * c2 * au2)
    return jnp.where(fhmn == 0, 0.0,
                     jnp.where(ga <= fhmx, fhmn * c1, ssmin_big_g))


def _bdsqr_shifted_sweep(d: jax.Array, e: jax.Array, ll, m, shift):
    """One shifted implicit-QR bulge-chase sweep on the active block
    [ll, m+1] of the real upper bidiagonal (LAPACK dbdsqr's downward
    shifted recurrence), gated so indices outside the block pass
    through untouched (rotations emitted as identity). Verified
    identity: bidiag' = Gl^T bidiag Gr with the chains below."""
    n = d.shape[0]
    dt = d.dtype

    def body(carry, i):
        d, e, f, g = carry
        active = (i >= ll) & (i <= m)
        dll = d[i]
        dll_s = jnp.where(dll == 0, jnp.ones((), dt), dll)
        f0 = (jnp.abs(dll) - shift) * (jnp.sign(dll) + shift / dll_s)
        f = jnp.where(i == ll, f0, f)
        g = jnp.where(i == ll, e[i], g)
        cosr, sinr, r = _lartg(f, g, dt)
        im1 = jnp.maximum(i - 1, 0)
        e = e.at[im1].set(jnp.where(active & (i > ll), r, e[im1]))
        f2 = cosr * d[i] + sinr * e[i]
        e_i = cosr * e[i] - sinr * d[i]
        g2 = sinr * d[i + 1]
        d_i1 = cosr * d[i + 1]
        cosl, sinl, r2 = _lartg(f2, g2, dt)
        f3 = cosl * e_i + sinl * d_i1
        d_i1b = cosl * d_i1 - sinl * e_i
        ip1 = jnp.minimum(i + 1, n - 2)
        g3 = jnp.where(i < m, sinl * e[ip1], g)
        e_ip1 = jnp.where(i < m, cosl * e[ip1], e[ip1])
        d = d.at[i].set(jnp.where(active, r2, d[i]))
        d = d.at[i + 1].set(jnp.where(active, d_i1b, d[i + 1]))
        e = e.at[i].set(jnp.where(active, e_i, e[i]))
        e = e.at[ip1].set(jnp.where(active & (i < m), e_ip1, e[ip1]))
        f = jnp.where(active, f3, f)
        g = jnp.where(active, g3, g)
        one, zero = jnp.ones((), dt), jnp.zeros((), dt)
        return (d, e, f, g), (jnp.where(active, cosr, one),
                              jnp.where(active, sinr, zero),
                              jnp.where(active, cosl, one),
                              jnp.where(active, sinl, zero))

    (d, e, f, g), rots = jax.lax.scan(
        body, (d, e, jnp.zeros((), dt), jnp.zeros((), dt)),
        jnp.arange(n - 1))
    e = e.at[m].set(f)
    return d, e, rots


#: above this size the QR iteration's O(k^4) transform
#: accumulation loses to the fused O(k^3) SVD
BDSQR_QR_MAX_N = 512


def bdsqr_qr(d: jax.Array, e: jax.Array, maxit_factor: int = 12):
    """Real bidiagonal SVD by the shifted implicit QR ITERATION
    (reference src/bdsqr.cc -> LAPACK bdsqr; SURVEY §2.6): per pass,
    negligible off-diagonals deflate to exact zero, the trailing
    active block [ll, m] is located, the shift comes from its trailing
    2x2 (dlas2, zeroed when it would cost relative accuracy), and one
    gated bulge-chase sweep runs. Each sweep's rotation chains compose
    into two orthogonal matrices applied as ONE matmul each
    (_givens_chain_matrix), so transform accumulation is MXU work even
    though the d/e recurrence is sequential. Converges in ~2-3 sweeps
    per singular value. Returns (s, Gu, Gvh, info) descending with
    bidiag(d, e) = Gu @ diag(s) @ Gvh; info > 0 counts the
    off-diagonals still above tolerance at the iteration cap
    (LAPACK bdsqr INFO convention)."""
    n = d.shape[0]
    dt = d.dtype
    eps = jnp.finfo(dt).eps
    tol = 20.0 * eps
    ids = jnp.arange(n - 1)

    def clamp(d, e):
        keep = jnp.abs(e) > tol * (jnp.abs(d[:-1]) + jnp.abs(d[1:]))
        return jnp.where(keep, e, 0.0)

    def cond(carry):
        d, e, Gu, Gvh, it = carry
        return jnp.any(clamp(d, e) != 0) & (it < maxit_factor * n)

    def body(carry):
        d, e, Gu, Gvh, it = carry
        e = clamp(d, e)
        nz = e != 0
        m = jnp.max(jnp.where(nz, ids, -1))
        ll = jnp.max(jnp.where((~nz) & (ids < m), ids, -1)) + 1
        mm = jnp.clip(m, 0, n - 2)
        shift = _dlas2_min(d[mm], e[mm], d[jnp.minimum(mm + 1, n - 1)])
        dll = d[ll]
        dll_s = jnp.where(dll == 0, jnp.ones((), dt), dll)
        # relative-accuracy safeguard (LAPACK): zero shift when it is
        # negligible against the block's leading entry
        shift = jnp.where((shift / dll_s) ** 2 < eps, 0.0, shift)
        d, e, (cr, sr, cl, sl) = _bdsqr_shifted_sweep(d, e, ll, m,
                                                      shift)
        if apply_chain is not None:
            # blocked route: Gu @ Gl right-applies the left chain;
            # Gr^T @ Gvh right-applies the right chain to Gvh^T
            Gu = apply_chain(Gu, cl, sl)
            Gvh = apply_chain(Gvh.T, cr, sr).T
        else:
            Gr = _givens_chain_matrix(cr, sr, n, dt)
            Gl = _givens_chain_matrix(cl, sl, n, dt)
            # B' = Gl^T B Gr  =>  B = Gl B' Gr^T: accumulate
            Gu = jnp.matmul(Gu, Gl,
                            precision=jax.lax.Precision.HIGHEST)
            Gvh = jnp.matmul(Gr.T, Gvh,
                             precision=jax.lax.Precision.HIGHEST)
        return d, e, Gu, Gvh, it + 1

    # route arbitrated once at trace time — op 'bdsqr', cold dense
    apply_chain = _select_chain_apply("bdsqr", n, n, dt)
    eye = jnp.eye(n, dtype=dt)
    d, e, Gu, Gvh, _ = jax.lax.while_loop(
        cond, body, (d, e, eye, eye, jnp.zeros((), jnp.int32)))
    # LAPACK bdsqr info: count of off-diagonals still above tolerance
    # (nonzero only if the iteration cap was exhausted)
    info = jnp.sum(clamp(d, e) != 0).astype(jnp.int32)
    # signs into Gu, then descending order
    sgn = jnp.where(d < 0, -jnp.ones((), dt), jnp.ones((), dt))
    s = jnp.abs(d)
    Gu = Gu * sgn[None, :]
    order = jnp.argsort(-s)
    return s[order], Gu[:, order], Gvh[order, :], info


def bdsqr(B: BidiagResult, opts: OptionsLike = None,
          return_info: bool = False):
    """Bidiagonal QR iteration (reference src/bdsqr.cc, slate.hh:1082).
    The real QR iteration (bdsqr_qr: shifted implicit sweeps with
    deflation, transforms applied as one composed-chain matmul per
    sweep) runs on the CPU/host path; on TPU its data-dependent
    while_loop of small sweeps is latency-bound, so the fused XLA SVD
    of the bidiagonal runs there instead (and the TPU production path
    is svd's QDWH, which skips the staged pipeline entirely).

    return_info=True returns (result, info), LAPACK bdsqr INFO
    convention: 0 converged; k > 0 counts off-diagonals still above
    tolerance at the iteration cap (QR-iteration path only — the
    fused path always reports 0)."""
    d, e = B.d, B.e
    k = d.shape[0]
    info = jnp.zeros((), jnp.int32)
    from ..ops.pallas_kernels import _on_tpu
    # k cap: the QR iteration's transform accumulation costs two
    # (k, k) matmuls per sweep at ~2-3 sweeps per singular value —
    # O(k^4); beyond the cap the fused O(k^3) SVD wins
    if not _on_tpu() and 1 < k <= BDSQR_QR_MAX_N \
            and not jnp.issubdtype(d.dtype, jnp.complexfloating):
        s, u2, vh2, info = bdsqr_qr(d, e)
    else:
        if k > 1 and not _on_tpu():
            # on TPU this branch is the documented default (module
            # doc) — warning there would fire on every staged SVD;
            # the routing surprise worth surfacing is the driver-level
            # MethodSVD.QRIteration request, warned in svd()
            import warnings
            warnings.warn(
                "bdsqr: n=%d exceeds BDSQR_QR_MAX_N=%d (or dtype is "
                "complex); the fused XLA SVD of the bidiagonal runs "
                "instead of rotation-chain QR iteration. Singular "
                "values match; the rotation-chain INFO convention "
                "does not apply (info=0)." % (k, BDSQR_QR_MAX_N),
                stacklevel=2)
        bid = jnp.diag(d) + jnp.diag(e, 1)
        u2, s, vh2 = jax.lax.linalg.svd(bid, full_matrices=False)
    U = None
    Vh = None
    if B.U is not None:
        u = B.U.to_dense()[:, :k] @ u2.astype(B.U.dtype)
        U = TiledMatrix.from_dense(u, B.U.mb, B.U.nb)
    if B.Vh is not None:
        vh = vh2.astype(B.Vh.dtype) @ B.Vh.to_dense()[:k, :]
        Vh = TiledMatrix.from_dense(vh, B.Vh.mb, B.Vh.nb)
    res = SVDResult(s, U, Vh)
    return (res, info) if return_info else res


def unmbr_ge2tb(U: TiledMatrix, Vh: TiledMatrix, C: TiledMatrix,
                side_left: bool = True,
                opts: OptionsLike = None):
    """Apply the ge2tb bidiagonalization transforms to C (reference
    src/unmbr_ge2tb.cc, slate.hh:1052). ge2tb returns accumulated U/Vh,
    so this is a distributed matmul with the requested factor."""
    f = U if side_left else Vh
    c = C.to_dense()
    m = f.to_dense()
    out = jnp.matmul(m, c, precision=jax.lax.Precision.HIGHEST) \
        if side_left else jnp.matmul(c, m,
                                     precision=jax.lax.Precision.HIGHEST)
    return _store(C, out)


def unmbr_tb2bd(U: TiledMatrix, Vh: TiledMatrix, C: TiledMatrix,
                side_left: bool = True, opts: OptionsLike = None):
    """Reference src/unmbr_tb2bd.cc (slate.hh:1330); tb2bd composes
    its stage-2 transforms into the returned U/Vh (see tb2bd), so the
    apply is the same accumulated-factor matmul as unmbr_ge2tb."""
    return unmbr_ge2tb(U, Vh, C, side_left, opts)
