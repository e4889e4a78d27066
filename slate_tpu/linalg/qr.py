"""QR / LQ / least squares (reference src/geqrf.cc, gelqf.cc, unmqr.cc,
unmlq.cc, cholqr.cc, gels.cc; SURVEY §3.4).

TPU-native design. The reference's QR is: device-capable Householder
panel (internal::geqrf, geqrf.cc:153), a binary-tree reduction across the
panel's ranks (internal::ttqrt, geqrf.cc:161), then compact-WY trailing
updates (unmqr/ttmqr, geqrf.cc:209-251) with lookahead. Here:

- the panel is a `lax.fori_loop` of masked Householder reflections over
  the full distributed panel column — XLA's tree-reduced column norms play
  the role of the ttqrt rank tree;
- the T factor (compact WY) is built by a masked forward recurrence
  (lapack larft equivalent);
- the trailing update C -= V T^H (V^H C) is two large MXU matmuls,
  statically unrolled per panel like the reference's task loop.

Packed format follows LAPACK/SLATE: V below the diagonal (v0 = 1
implicit), R on/above; taus returned separately (the reference's
TriangularFactors hold per-panel T matrices — we rebuild T on the fly,
trading a small recompute for not storing mt*nb^2 of T tiles in HBM).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
# packed-Householder geqrf: jax 0.9 exposes only qr/householder_product
# publicly, so the primitive comes from the private module; imported
# at module load so a jax that moves it fails here, not mid-panel
from jax._src.lax.linalg import geqrf as _geqrf

from ..core.enums import Diag, MatrixType, Side, Uplo
from ..core.exceptions import SlateError
from ..core.methods import MethodFactor, MethodGels
from ..core.options import Option, OptionsLike, get_option
from ..core.tiles import TiledMatrix, ceil_div
from ..obs import events as obs_events
from ..obs import metrics as obs_metrics
from ..obs.events import instrument_driver
from ..ops.householder import reflect as _reflect
from .blas3 import _store, trsm
from .chol import potrf


class QRFactors(NamedTuple):
    """Packed Householder factor (V below the diagonal, R on/above)
    plus taus (reference geqrf output A + T). ``Q`` is an OPTIONAL
    explicit orthogonal factor: the packed contract is the default
    (faster and O(M*N); an explicit square form was quadratic in
    rows, PERF.md), but unmqr applies an explicit Q by one matmul —
    square, or THIN (M, K): the mesh-TSQR grid route
    (_geqrf_tsqr_grid) returns the thin orthonormal factor, whose
    apply is the isometry (output rows past K are exact zeros)."""
    QR: TiledMatrix
    taus: jax.Array        # (n_pad,)
    Q: "TiledMatrix | None" = None


class LQFactors(NamedTuple):
    LQ: TiledMatrix
    taus: jax.Array        # (m_pad,)


def _native_geqrf(a: jax.Array):
    """XLA's geqrf primitive (packed Householder + taus — LAPACK on
    CPU, blocked expander on TPU), or None where its dtype support
    ends. Measured v5e (PERF.md): 0.42 ms on a 4096x256 panel,
    ~4x faster than the fused Pallas panel kernel — it carries the
    whole blocked geqrf to 11 TF/s at n=4096 (vs 5.7 round-2)."""
    # geqrf's custom-call dtype set matches LuDecomposition's
    # (methods.py native_lu_dtype_ok) — bf16 falls back
    if not MethodFactor.native_lu_dtype_ok(a.dtype):
        return None
    packed, taus = _geqrf(a)
    w = a.shape[1]
    if taus.shape[0] < w:
        # wide panels (m < w) carry only min(m, w) reflectors; pad the
        # tail with tau = 0 (exact identities) to keep the (w,) contract
        taus = jnp.zeros((w,), taus.dtype).at[:taus.shape[0]].set(taus)
    return packed, taus


def _qr_panel(a: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Householder QR of an (m, w) panel: XLA's native geqrf first
    (see _native_geqrf), then the fused Pallas dispatch for dtypes it
    cannot take (bf16), then a masked fori_loop of sequential
    reflections, vectorized over rows (reference internal::geqrf
    panel kernel)."""
    from ..ops import pallas_kernels as pk
    native = _native_geqrf(a)
    if native is not None:
        return native
    m, w = a.shape
    # routing consults the TPU gate explicitly: off-TPU the kernel
    # would RUN (interpret mode, pallas_kernels module doc) but must
    # not change the driver's cold route
    if pk.qr_panel_eligible(m, w, a.dtype):
        fused = pk.qr_panel(a)
        if fused is not None:
            return fused
    rows = jnp.arange(m)

    def body(j, carry):
        a, taus = carry
        x = jnp.where(rows >= j, a[:, j], 0)
        v, tau, beta = _reflect(x, rows, j)
        # apply H = I - tau v v^H to the columns to the right
        cols = jnp.arange(w)
        vha = jnp.matmul(jnp.conj(v), a,
                         precision=jax.lax.Precision.HIGHEST)   # (w,)
        upd = tau * jnp.outer(v, jnp.where(cols > j, vha, 0))
        a = a - upd
        # store beta on the diagonal, v below it
        below = rows > j
        newcol = jnp.where(below, v, a[:, j]).at[j].set(beta)
        a = a.at[:, j].set(newcol)
        taus = taus.at[j].set(tau)
        return a, taus

    taus0 = jnp.zeros((w,), a.dtype)
    return jax.lax.fori_loop(0, w, body, (a, taus0))


def _qr_panel_blocked(a: jax.Array, ib: int = 128
                      ) -> Tuple[jax.Array, jax.Array]:
    """Panel factorization: one native XLA geqrf when its dtype
    support allows (the fast path, PERF.md), else ib-wide sub-panels
    (each one fused Pallas dispatch on TPU) with compact-WY updates of
    the remaining panel columns — the reference's InnerBlocking
    (geqrf ib option) realized as kernel-width blocking."""
    native = _native_geqrf(a)
    if native is not None:
        return native
    m, w = a.shape
    if w <= ib:
        return _qr_panel(a)
    taus = jnp.zeros((w,), a.dtype)
    for s in range(0, w, ib):
        e = min(s + ib, w)
        sub, stau = _qr_panel(a[s:, s:e])
        a = a.at[s:, s:e].set(sub)
        taus = taus.at[s:e].set(stau)
        if e < w:
            V = _panel_V(sub, 0)
            T = _larft(V, stau)
            C = a[s:, e:]
            W = jnp.matmul(jnp.conj(V.T), C,
                           precision=jax.lax.Precision.HIGHEST)
            W = jnp.matmul(jnp.conj(T.T), W,
                           precision=jax.lax.Precision.HIGHEST)
            a = a.at[s:, e:].set(
                C - jnp.matmul(V, W, precision=jax.lax.Precision.HIGHEST))
    return a, taus


def _larft(V: jax.Array, taus: jax.Array) -> jax.Array:
    """Compact-WY T factor: Q = I - V T V^H (lapack larft; reference
    per-panel TriangularFactors).

    Closed form instead of the sequential column recurrence:
    T^{-1} = diag(1/tau) + striu(V^H V), so T is one Gram matmul plus
    one small triangular inversion (blocked.invert_triangular — one
    XLA solve at panel widths). Reflectors with tau == 0 (H = I) are
    masked out of the Gram matrix and of T, which reproduces LAPACK's
    skip-inactive semantics."""
    w = V.shape[1]
    vhv = jnp.matmul(jnp.conj(V.T), V,
                     precision=jax.lax.Precision.HIGHEST)     # (w, w)
    active = taus != 0
    act2 = active[:, None] & active[None, :]
    safe = jnp.where(active, taus, jnp.ones((), taus.dtype))
    tinv = jnp.diag(1.0 / safe) + jnp.triu(jnp.where(act2, vhv, 0), 1)
    from .blocked import invert_triangular
    T = invert_triangular(tinv, lower=False)
    return jnp.where(act2, T, 0)


def _geqrf_carry(a: jax.Array, nb: int, kmax: int, ib: int
                 ) -> Tuple[jax.Array, jax.Array]:
    """Single-device blocked Householder QR carrying the SHRINKING
    trailing matrix as loop state: each step's only big write is the
    compact-WY update output itself, avoiding the O(nt * n^2) extra
    HBM traffic of functional full-matrix slice updates (measured 2x
    on v5e, PERF.md 'composition experiments'). Reflector k's rows
    live at/below its diagonal, so after panel k the top nb rows are
    final R rows and drop out of the carried block — the same
    shrinking-trail shape as the LU carry driver."""
    HI = jax.lax.Precision.HIGHEST
    M, N = a.shape
    nt = ceil_div(kmax, nb)
    trail = a
    panels = []
    taus_l = []
    rtops = []
    for k in range(nt):
        k0, k1 = k * nb, min((k + 1) * nb, kmax)
        w = k1 - k0
        pan, ptau = _qr_panel_blocked(trail[:, :w], ib=ib)
        panels.append(pan)
        taus_l.append(ptau)
        if k1 < N:
            V = _panel_V(pan, 0)
            T = _larft(V, ptau)
            rest = trail[:, w:]
            W = jnp.matmul(jnp.conj(V.T), rest, precision=HI)
            W = jnp.matmul(jnp.conj(T.T), W, precision=HI)
            rest = rest - jnp.matmul(V, W, precision=HI)
            rtops.append(rest[:w])
            trail = rest[w:]
    from .blocked import assemble_packed
    out = assemble_packed(panels, rtops, nb, kmax, M, N, a.dtype)
    taus = jnp.concatenate(taus_l)
    npad = min(M, N)
    if taus.shape[0] < npad:     # padded-length contract (tau=0 pad)
        taus = jnp.zeros((npad,), taus.dtype).at[:taus.shape[0]].set(taus)
    return out, taus


def _panel_V(a_panel: jax.Array, j0: int) -> jax.Array:
    """Extract unit-lower V from packed panel rows [j0:, :]."""
    m, w = a_panel.shape
    ii = jnp.arange(m)[:, None]
    jj = jnp.arange(w)[None, :]
    V = jnp.where(ii - j0 > jj, a_panel, 0)
    V = V + (jnp.asarray((ii - j0) == jj, a_panel.dtype))
    return V


#: block-step count above which geqrf switches to the fixed-shape
#: fori_loop form (O(1) program size; see blocked.CHOL_SCAN_THRESHOLD)
QR_SCAN_THRESHOLD = 64


def _roll_live(x: jax.Array, shift, live, idx: jax.Array) -> jax.Array:
    """Roll rows of x by -shift (diagonal to index 0) and zero the dead
    rows at/past `live` — THE masking discipline every fixed-shape scan
    form relies on: dead rows at exact zero make all full-size update
    matmuls contribute exact zeros outside the live window."""
    rolled = jnp.roll(x, -shift, axis=0)
    return jnp.where((idx < live)[:, None], rolled, 0)


def _rolled_panel_factor(colblk: jax.Array, shift, live,
                         idx: jax.Array, ib: int = 128):
    """Shared scan-form panel step: roll a full-height column block so
    its diagonal sits at row 0, mask dead rows, QR-factor it, and build
    the (dead-row-masked) V and T. Returns (packed, V, T, taus).
    Used by the geqrf/he2hb/ge2tb fixed-shape loops."""
    rolled = _roll_live(colblk, shift, live, idx)
    packed, taus = _qr_panel_blocked(rolled, ib=ib)
    V = _panel_V(packed, 0)
    # short last panels: mask unit-diagonal entries past the live rows
    V = jnp.where((idx < live)[:, None], V, 0)
    T = _larft(V, taus)
    return packed, V, T, taus


def _geqrf_scan(a: jax.Array, nb: int, kmax: int, grid=None,
                ib: int = 128):
    """Blocked Householder QR as ONE compiled block step iterated by
    fori_loop (compile-time-safe form for huge nt): the panel is sliced
    full-height and rolled so its diagonal sits at row 0 (the packing
    the fused panel kernel assumes, wrapped factored rows masked to
    zero), and the compact-WY trailing update runs full-size with the
    already-factored columns masked out."""
    from ..parallel.sharding import constrain
    HI = jax.lax.Precision.HIGHEST
    M, N = a.shape
    nt = ceil_div(kmax, nb)
    rows = jnp.arange(M)
    cols = jnp.arange(N)
    # taus over-allocated to whole panels (padding columns yield tau=0)
    # and cropped by the caller
    taus = jnp.zeros((nt * nb,), a.dtype)

    def step(k, carry):
        a, taus = carry
        k0 = k * nb
        k1 = k0 + nb
        live = M - k0
        colblk = jax.lax.dynamic_slice(a, (0, k0), (M, nb))
        packed, V, T, ptau = _rolled_panel_factor(colblk, k0, live,
                                                  rows, ib=ib)
        taus = jax.lax.dynamic_update_slice(taus, ptau, (k0,))
        # trailing update on the rolled frame, factored columns masked
        ar = _roll_live(a, k0, live, rows)
        Cm = jnp.where((cols >= k1)[None, :], ar, 0)
        W = jnp.matmul(jnp.conj(T.T),
                       jnp.matmul(jnp.conj(V.T), Cm, precision=HI),
                       precision=HI)
        upd = jnp.matmul(V, W, precision=HI)
        upd = jnp.roll(upd, k0, axis=0)
        a = constrain(a - upd, grid)
        # write the packed panel back into rows >= k0
        unpacked = jnp.roll(
            jnp.where((rows < live)[:, None], packed, 0), k0, axis=0)
        cur = jax.lax.dynamic_slice(a, (0, k0), (M, nb))
        newblk = jnp.where((rows >= k0)[:, None], unpacked, cur)
        a = jax.lax.dynamic_update_slice(a, newblk, (0, k0))
        return a, taus

    return jax.lax.fori_loop(0, nt, step, (a, taus))


def geqrf_default_nb(kmax: int, tile_nb: int) -> int:
    """Frozen single-device algorithmic blocking for geqrf: nb grows
    with n to hold the carry step count near 16 — at n=16384 the
    64-step nb=256 unroll RESOURCE_EXHAUSTS HBM (too many
    concurrently-live step intermediates under XLA's scheduler) while
    nb=512/1024 run at 18.5/19.0 TF/s, and nb=1024 is also the
    fastest (PERF.md round-4 sweep); at n <= 8192 the 256/512 forms
    measure within noise of each other, so the policy is monotone in
    n: 256/512/1024 at 4096/8192/16384. ONE definition shared by the
    driver and bench --tune's frozen-baseline label."""
    from ..core.tiles import round_up
    return max(min(tile_nb, 256),
               min(round_up(ceil_div(kmax, 16), 128), 1024))


@instrument_driver("geqrf")
def geqrf(A: TiledMatrix, opts: OptionsLike = None, *,
          _allow_tsqr: bool = True) -> QRFactors:
    """Blocked Householder QR (reference src/geqrf.cc:26, slate.hh:953).
    With Option.Grid, each panel's compact-WY trailing update is
    sharding-constrained over the mesh (the reference's unmqr/ttmqr
    trailing tasks, geqrf.cc:209-251); panels run replicated like the
    reference's panel rank set — except tall-skinny shapes, which take
    the mesh TSQR tree (_geqrf_tsqr_grid, explicit thin-Q factors).
    _allow_tsqr=False (internal) forces the packed-Householder routes:
    gelqf's conjugate-dual construction carries only the packed array
    + taus, so an explicit-Q result would silently apply identity
    reflectors downstream.

    Routing altitude: this driver factors DEVICE-RESIDENT matrices
    (HBM-bounded). Beyond-HBM host-resident problems take
    ooc.geqrf_ooc — single-device streamed, or 2D-block-cyclic
    sharded over a mesh via its ``grid=`` route (MethodOOC
    arbitration, dist/shard_ooc.py)."""
    from ..parallel.sharding import constrain
    grid = get_option(opts, Option.Grid, None)
    r = A.uniform().resolve()    # non-uniform tiles re-tile at entry
    a = r.data
    M, N = a.shape
    nb = r.nb
    method = get_option(opts, Option.MethodFactor, MethodFactor.Auto)
    if method is MethodFactor.Fused and grid is not None:
        import warnings
        warnings.warn(
            "geqrf: MethodFactor.Fused is single-device; a Grid was "
            "given, so the Tiled blocked path runs instead",
            stacklevel=2)
    requested = method
    if grid is not None and _allow_tsqr \
            and method in (MethodFactor.Auto, MethodFactor.Tiled) \
            and not jnp.issubdtype(a.dtype, jnp.complexfloating):
        # tall-skinny on a mesh: the dist/tsqr.py tree replaces panel
        # replication outright — the whole matrix is one panel, each
        # device QRs its own row chunk, and only (n, n) R factors ride
        # the ppermute tree (the reference's ttqrt reduction,
        # geqrf.cc:161,220, instead of the replicated panel rank set).
        # The aspect gate is a tunable ('tsqr'/'panel_aspect'): below
        # it the trailing-update work dominates and the blocked Tiled
        # path with sharding constraints stays the right shape.
        # Explicit-Q factors come back (QRFactors.Q — a cross-device
        # tree's V lives in per-level TriangularFactors the packed
        # single-array contract cannot carry); complex stays blocked
        # until the tree's leaf QR is exercised for it.
        from ..dist import tsqr as dtsqr
        from ..tune.select import tuned_int
        aspect = tuned_int("tsqr", "panel_aspect", 4, opts=opts,
                           n=r.n, dtype=a.dtype)
        if r.n >= 1 and r.m >= aspect * r.n \
                and dtsqr.eligible(grid, (r.m, r.n)):
            obs_events.note(factor="tiled", form="tsqr")
            return _geqrf_tsqr_grid(grid, r, opts)
    if grid is None and method is MethodFactor.Auto:
        # measured crossover (PERF.md): below ~4k the one-call native
        # geqrf edges out the blocked carry form (8.5 vs 9.2 ms at
        # n=4096 v5e); above it the carry form's bigger trailing
        # matmuls win (43.0 vs 46.2 ms at n=8192). The crossover is a
        # tunable threshold whose shipped value lives in the FROZEN
        # table (tune/cache.py, 4096) — no fallback here, so the
        # table is the single source of truth.
        from ..tune.select import resolve
        fused_max_n = int(resolve("geqrf", "fused_max_n", opts=opts,
                                  n=min(r.m, r.n), dtype=a.dtype))
        if min(r.m, r.n) <= fused_max_n:
            method = MethodFactor.Fused
    if method is MethodFactor.Fused and grid is None:
        # single fused XLA program: ONE whole-matrix native geqrf,
        # keeping the packed-Householder contract (unmqr/gels
        # unchanged). The previous explicit-Q form (full_matrices
        # jax qr) was retired: it allocated an (M, M) Q — quadratic
        # in rows for the tall-skinny gels case — and measured SLOWER
        # than the blocked packed path (12.7 vs 8.3 ms at n=4096,
        # PERF.md). Falls through to the blocked path for dtypes the
        # native kernel cannot take (bf16).
        native = _native_geqrf(a)
        if native is not None:
            obs_events.note(factor="fused", form="native")
            packed, ntaus = native
            out = dataclasses.replace(r, data=packed,
                                      mtype=MatrixType.General)
            return QRFactors(out, ntaus[:min(M, N)])
        if requested is MethodFactor.Fused:
            # only a USER-requested Fused warrants the noise; the Auto
            # resolution above falls through silently by design
            import warnings
            warnings.warn(
                "geqrf: XLA's native geqrf does not implement "
                f"{jnp.dtype(a.dtype).name}; falling back to the "
                "Tiled blocked path", stacklevel=2)
    kmax = max(min(r.m, r.n), 1)     # number of reflectors (logical)
    from ..core.options import get_option_tuned
    ib = get_option_tuned(opts, Option.InnerBlocking, "geqrf",
                          n=kmax, dtype=a.dtype)  # registry default
    if grid is None:
        # single-device algorithmic blocking, decoupled from the
        # storage tile size and scaled with n (PERF.md round-4b),
        # overridable via Option.BlockSize. The carry form handles any
        # width; only when its step count would break the program-size
        # or memory bound does the scan form take over (whose
        # fixed-width column blocks additionally need the blocking to
        # divide the padded width — fall back to the tile size when it
        # doesn't).
        from ..tune.select import tuned_int
        nb_frozen = geqrf_default_nb(kmax, nb)
        # explicit option > cached measurement > the frozen n-scaled
        # formula; an explicit 0 keeps its historical "use the
        # default" meaning
        cand = tuned_int("geqrf", "nb", nb_frozen, opts=opts,
                         option=Option.BlockSize, n=kmax,
                         dtype=a.dtype) or nb_frozen
        # above 8192 reflectors the measured OOM regime is the STEP
        # COUNT (16384/64-step died, 32-step fit with margin): tall
        # kmax > 16384 would crawl back to 32-64 steps under the 1024
        # nb cap, so the carry gate tightens there and the scan form
        # (O(1) live intermediates) takes over instead
        step_cap = QR_SCAN_THRESHOLD if kmax <= 16384 else 16
        if ceil_div(kmax, cand) > step_cap and r.m < r.n:
            # wide shapes cannot take the scan form (it requires every
            # column block to get factored, m >= n), so keep the carry
            # fast path and bound the program size by widening the
            # panels until the step count fits the threshold
            cand = round_up(ceil_div(kmax, step_cap), 128)
        if ceil_div(kmax, cand) <= step_cap:
            obs_events.note(factor="tiled", form="carry", nb=cand, ib=ib)
            packed, taus = _geqrf_carry(a, cand, kmax, ib)
            out = dataclasses.replace(r, data=packed,
                                      mtype=MatrixType.General)
            return QRFactors(out, taus[:min(M, N)])
        # tall/square above the threshold: the fixed-shape scan form
        # (O(1) program size; its fixed-width column blocks need the
        # blocking to divide the padded width — tile size otherwise)
        nb_scan = cand if N % cand == 0 else nb
        obs_events.note(factor="tiled", form="scan", nb=nb_scan, ib=ib)
        a, taus = _geqrf_scan(a, nb_scan, kmax, None, ib=ib)
        out = dataclasses.replace(r, data=a,
                                  mtype=MatrixType.General)
        return QRFactors(out, taus[:min(M, N)])
    nt = ceil_div(kmax, nb)
    if grid is not None and nt > QR_SCAN_THRESHOLD and r.m >= r.n:
        obs_events.note(factor="tiled", form="scan", nb=nb, ib=ib)
        a, taus = _geqrf_scan(a, nb, kmax, grid, ib=ib)
        out = dataclasses.replace(r, data=a, mtype=MatrixType.General)
        return QRFactors(out, taus[:min(M, N)])
    obs_events.note(factor="tiled", form="unrolled", nb=nb, ib=ib)
    taus = jnp.zeros((min(M, N),), a.dtype)
    for k in range(nt):
        k0, k1 = k * nb, min((k + 1) * nb, kmax)
        panel, ptau = _qr_panel_blocked(a[k0:, k0:k1], ib=ib)
        a = a.at[k0:, k0:k1].set(panel)
        taus = taus.at[k0:k1].set(ptau)
        if k1 < N:
            V = _panel_V(panel, 0)
            T = _larft(V, ptau)
            # C -= V T^H (V^H C)   (Q^H C with Q = I - V T V^H)
            C = a[k0:, k1:]
            W = jnp.matmul(jnp.conj(V.T), C,
                           precision=jax.lax.Precision.HIGHEST)
            W = jnp.matmul(jnp.conj(T.T), W,
                           precision=jax.lax.Precision.HIGHEST)
            C = C - jnp.matmul(V, W, precision=jax.lax.Precision.HIGHEST)
            a = constrain(a.at[k0:, k1:].set(C), grid)
    out = dataclasses.replace(r, data=a, mtype=MatrixType.General)
    return QRFactors(out, taus)


def _geqrf_tsqr_grid(grid, r: TiledMatrix, opts) -> QRFactors:
    """Tall-skinny grid geqrf via the mesh TSQR tree (dist/tsqr.py):
    per-device chunk QR, log-depth ppermute R-combine, local Q
    down-sweep. R lands in the packed slot (triu, V region zero) and
    the thin orthonormal factor in QRFactors.Q, which unmqr applies
    as the isometry — so gels_qr and explicit callers compose
    unchanged. taus are all zero (tau = 0 reflectors are exact
    identities), keeping the packed-contract invariants for code
    that only reads R."""
    from ..dist import tsqr as dtsqr
    a = r.data[:, :r.n]          # padded rows stay: zero rows are exact
    Qd, R = dtsqr.tsqr(grid, a, opts=opts)
    M, N = r.data.shape
    packed = jnp.zeros((M, N), a.dtype).at[:r.n, :r.n].set(R)
    out = dataclasses.replace(r, data=packed, mtype=MatrixType.General)
    taus = jnp.zeros((min(M, N),), a.dtype)
    Qtm = TiledMatrix.from_dense(Qd, r.mb, r.nb)
    return QRFactors(out, taus, Q=Qtm)


def _unmqr_scan(a: jax.Array, taus: jax.Array, nb: int, kmax: int,
                c: jax.Array, left: bool, trans: bool,
                forward: bool) -> jax.Array:
    """Apply Q/Q^H as ONE compiled panel step iterated by fori_loop
    (compile-time-safe form of the unmqr loop for huge nt — program
    size O(1) in nt, completing the huge-n chain for gels and the
    heev/svd back-transforms).

    Same roll discipline as _geqrf_scan: the k-th panel column block is
    rolled so its diagonal sits at row 0 and the wrapped R rows are
    masked to zero, so V is full-height with exact zeros in dead rows —
    the rolled C update then contributes exact zeros outside rows/cols
    k0:, and no per-step shape depends on k."""
    HI = jax.lax.Precision.HIGHEST
    M = a.shape[0]
    nt = ceil_div(kmax, nb)
    rows = jnp.arange(M)
    # pad taus to whole panels (tau=0 reflectors are exact identities);
    # taus may carry the padded min(M,N) length — crop to the logical
    # reflector count first
    tpad = jnp.zeros((nt * nb,), taus.dtype).at[:kmax].set(taus[:kmax])

    def step(i, c):
        k = i if forward else nt - 1 - i
        k0 = k * nb
        live = M - k0
        colblk = jax.lax.dynamic_slice(a, (0, k0), (M, nb))
        V = _panel_V(_roll_live(colblk, k0, live, rows), 0)
        V = jnp.where((rows < live)[:, None], V, 0)
        tau = jax.lax.dynamic_slice(tpad, (k0,), (nb,))
        T = _larft(V, tau)
        Tm = jnp.conj(T.T) if trans else T
        if left:
            cr = jnp.roll(c, -k0, axis=0)
            W = jnp.matmul(jnp.conj(V.T), cr, precision=HI)
            W = jnp.matmul(Tm, W, precision=HI)
            upd = jnp.matmul(V, W, precision=HI)
            return c - jnp.roll(upd, k0, axis=0)
        cr = jnp.roll(c, -k0, axis=1)
        W = jnp.matmul(cr, V, precision=HI)
        W = jnp.matmul(W, Tm, precision=HI)
        upd = jnp.matmul(W, jnp.conj(V.T), precision=HI)
        return c - jnp.roll(upd, k0, axis=1)

    return jax.lax.fori_loop(0, nt, step, c)


@functools.partial(jax.jit, static_argnames=("nb", "kmax", "left", "trans"))
def _unmqr_apply(a: jax.Array, taus: jax.Array, c_log: jax.Array, *,
                 nb: int, kmax: int, left: bool, trans: bool
                 ) -> jax.Array:
    """The packed-factor apply of unmqr as ONE compiled program: C is
    padded to the factor's padded extent on the applied side, the
    ceil(kmax / nb) compact-WY panel updates run unrolled over the
    SHRINKING panels a[k0:, k0:k1] (reflector k has no rows above its
    diagonal, so panel k touches rows, or columns, k0: of C only),
    and the result is cropped back to C's logical shape. Called
    eagerly it is one dispatch where the same loop run from Python
    is some 50 a panel, each a gap on the device (PERF.md, PR 44: 398
    of tall-gels' 440 launches, the chip idle 0.09 s of a 0.52 s
    solve between them); under a caller's trace it inlines. Static
    on what shapes the program: the panel width, the reflector
    count, the side and the op."""
    HI = jax.lax.Precision.HIGHEST
    cm, cn = c_log.shape
    nt = ceil_div(kmax, nb)
    c = _pad_applied_side(c_log, a.shape[0], left)
    # Left Q^H C and right C Q consume panels forward; the other two in
    # reverse (Q = Q_1 Q_2 ... Q_nt from geqrf).
    forward = trans if left else not trans
    for k in (range(nt) if forward else reversed(range(nt))):
        k0, k1 = k * nb, min((k + 1) * nb, kmax)
        V = _panel_V(a[k0:, k0:k1], 0)
        T = _larft(V, taus[k0:k1])
        Tm = jnp.conj(T.T) if trans else T
        if left:
            Ck = c[k0:, :]
            W = jnp.matmul(jnp.conj(V.T), Ck, precision=HI)
            W = jnp.matmul(Tm, W, precision=HI)
            c = c.at[k0:, :].set(Ck - jnp.matmul(V, W, precision=HI))
        else:
            Ck = c[:, k0:]
            W = jnp.matmul(Ck, V, precision=HI)
            W = jnp.matmul(W, Tm, precision=HI)
            c = c.at[:, k0:].set(
                Ck - jnp.matmul(W, jnp.conj(V.T), precision=HI))
    return c[:cm, :cn]


def _pad_applied_side(c: jax.Array, M: int, left: bool) -> jax.Array:
    """C padded to the factor's padded extent M on the applied side:
    V's padded rows are zero, so the extra rows (columns) stay zero
    through the updates."""
    cm, cn = c.shape
    return jnp.pad(c, ((0, M - cm), (0, 0)) if left
                   else ((0, 0), (0, M - cn)))


def _unmqr_form(A: QRFactors) -> Tuple[str, int]:
    """(form, nt): which of unmqr's three applies these factors take,
    and over how many panels. `explicit`: one product with the
    factors' own Q (the mesh TSQR's). `scan`: one compiled panel step
    under fori_loop (_unmqr_scan), past QR_SCAN_THRESHOLD panels.
    `compiled`: one program unrolled over the shrinking panels
    (_unmqr_apply), everywhere else."""
    if A.Q is not None:
        return "explicit", 1
    r = A.QR.resolve()
    nt = ceil_div(max(min(r.m, r.n), 1), r.nb)
    # M >= nt*nb guarantees every rolled panel keeps its unit diagonal
    # inside live rows (always true for square tiles; odd mb<nb pads
    # fall back to the unrolled form)
    if nt > QR_SCAN_THRESHOLD and r.data.shape[0] >= nt * r.nb:
        return "scan", nt
    return "compiled", nt


def unmqr(side: Side, A: QRFactors, C: TiledMatrix, trans: bool = True,
          opts: OptionsLike = None) -> TiledMatrix:
    """Multiply C by Q or Q^H from geqrf (reference src/unmqr.cc,
    slate.hh:960). trans=True applies Q^H (the gels case).

    Three applies (_unmqr_form). Explicit-Q factors (the mesh TSQR's)
    apply by one matmul. Packed factors apply as ONE compiled program,
    _unmqr_apply: the compact-WY panel loop unrolled over the
    shrinking panels, the pad of C and the crop with it, so an eager
    caller dispatches once and the device does not wait between some
    50 small operations a panel.
    Past QR_SCAN_THRESHOLD panels the program's size is bounded
    instead by _unmqr_scan, one panel step under fori_loop. The scan
    form is NOT the default: a fixed-shape step rolls every panel to
    the full height M and updates all of C, so it multiplies
    nt * M * nb * w where the shrinking panels need half of that on a
    square factor, and it carries two rolls of C a step; it exists
    for the step counts at which an unrolled program would not
    compile in reasonable time or memory."""
    form, nt = _unmqr_form(A)
    if form == "explicit":
        HI = jax.lax.Precision.HIGHEST
        q = A.Q.to_dense()
        # square Q: the classical orthogonal apply. A THIN (M, K) Q
        # (the mesh-TSQR factors) applies as the ISOMETRY: the operand
        # is zero-padded/cropped to the rows qm consumes and the
        # result to C's logical extent — rows (cols) past K come out
        # exact zero, which is precisely the gels contract (only
        # (Q^H B)[:n] is meaningful).
        qm = jnp.conj(q.T) if trans else q
        c_log = C.to_dense()
        cm, cn = c_log.shape

        def fit(x, count, axis):
            if x.shape[axis] > count:
                return (x[:count] if axis == 0 else x[:, :count])
            pad = [(0, 0), (0, 0)]
            pad[axis] = (0, count - x.shape[axis])
            return jnp.pad(x, pad)

        if side is Side.Left:
            y = jnp.matmul(qm, fit(c_log, qm.shape[1], 0), precision=HI)
            return _store(C, fit(y, cm, 0))
        y = jnp.matmul(fit(c_log, qm.shape[0], 1), qm, precision=HI)
        return _store(C, fit(y, cn, 1))
    r = A.QR.resolve()
    a = r.data
    nb = r.nb
    kmax = max(min(r.m, r.n), 1)     # number of reflectors (logical)
    c_log = C.to_dense()
    left = side is Side.Left
    if form == "compiled":
        return _store(C, _unmqr_apply(a, A.taus, c_log, nb=nb, kmax=kmax,
                                      left=left, trans=trans))
    cm, cn = c_log.shape
    c = _unmqr_scan(a, A.taus, nb, kmax,
                    _pad_applied_side(c_log, a.shape[0], left), left, trans,
                    forward=trans if left else not trans)
    return _store(C, c[:cm, :cn])


def qr_multiply_by_q(*args, **kw):
    """Simplified-API name (reference simplified_api.hh:638)."""
    return unmqr(*args, **kw)


def gelqf(A: TiledMatrix, opts: OptionsLike = None) -> LQFactors:
    """LQ factorization A = L Q (reference src/gelqf.cc, slate.hh:980).
    Computed as the conjugate dual of QR on A^H; packed with V rows above
    the diagonal per LAPACK convention."""
    # the packed-Householder routes keep the contract unmlq's
    # compact-WY apply needs; the grid TSQR route does NOT (its
    # orthogonal factor is the explicit QRFactors.Q, which this dual
    # construction cannot carry — taus are zero there), so it is
    # explicitly disabled for the dual factorization
    F = geqrf(A.conj_transpose(), opts, _allow_tsqr=False)
    r = F.QR.resolve()
    packed = dataclasses.replace(
        r, data=jnp.conj(r.data.T), m=r.n, n=r.m, mb=r.nb, nb=r.mb)
    return LQFactors(packed, F.taus)


def unmlq(side: Side, A: LQFactors, C: TiledMatrix, trans: bool = False,
          opts: OptionsLike = None) -> TiledMatrix:
    """Multiply by Q from gelqf (reference src/unmlq.cc, slate.hh:987).
    Q_lq = (Q_qr)^H of the dual QR, so the op flag flips."""
    r = A.LQ.resolve()
    qr_packed = dataclasses.replace(
        r, data=jnp.conj(r.data.T), m=r.n, n=r.m, mb=r.nb, nb=r.mb)
    F = QRFactors(qr_packed, A.taus)
    # Q_lq = Q_dual^H, so applying Q_lq^(op) is the dual apply with the
    # trans flag flipped, same side.
    return unmqr(side, F, C, trans=not trans, opts=opts)


#: power and inverse iterations behind the condition estimate: each
#: is one (n, n) product or two triangular solves on one vector
_COND_ITERS = 8


@jax.jit
def _gram_factor_cond(gram: jax.Array, rfac: jax.Array) -> jax.Array:
    """What gels can observe of a Cholesky factor R of G = A^H A
    before it trusts it: an estimate of cond_2(A) = sqrt(lmax(G) /
    lmin(G)) from below, lmax by power iteration on G, lmin by
    inverse iteration through R (G^-1 w = R^-1 R^-H w), _COND_ITERS
    steps each from one fixed start vector, O(n^2) work a step. inf
    where the factorization broke down (a non-positive pivot leaves
    NaN from that column on: G is not numerically positive definite,
    cond(A)^2 eps >= 1)."""
    HI = jax.lax.Precision.HIGHEST
    n = gram.shape[0]
    rfac = jnp.triu(rfac[:n, :n])
    d = jnp.real(jnp.diagonal(rfac))
    ok = jnp.all(jnp.isfinite(d)) & jnp.all(d > 0)
    # fixed, sign-alternating, not aligned with any coordinate axis
    v0 = (jnp.cos(jnp.arange(n, dtype=d.dtype) * 0.7) + 0.5
          ).astype(gram.dtype)[:, None]

    def unit(v):
        return v / jnp.linalg.norm(v)

    def up(_, v):
        return unit(jnp.matmul(gram, v, precision=HI))

    def down(_, w):
        z = jax.lax.linalg.triangular_solve(
            rfac, w, left_side=True, lower=False, transpose_a=True,
            conjugate_a=True)
        return unit(jax.lax.linalg.triangular_solve(
            rfac, z, left_side=True, lower=False))

    v = jax.lax.fori_loop(0, _COND_ITERS, up, unit(v0))
    w = jax.lax.fori_loop(0, _COND_ITERS, down, unit(v0))
    lmax = jnp.linalg.norm(jnp.matmul(gram, v, precision=HI))
    # ||R w|| = sqrt(w^H G w): the Rayleigh quotient of G at w
    smin = jnp.linalg.norm(jnp.matmul(rfac, w, precision=HI))
    cond = jnp.sqrt(lmax) / smin
    return jnp.where(ok & jnp.isfinite(cond), cond, jnp.inf)


def _gram_factor(A: TiledMatrix, opts: OptionsLike, span,
                 explicit: bool = False):
    """(R, cond): R = chol(A^H A), upper (reference src/cholqr.cc;
    MethodCholQR variants select how A^H A is formed — one herk
    here), and the condition number it shows (_gram_factor_cond),
    inf where R is NaN, None under a jit trace, where no value can
    reach the host. The read of `cond` is the one host
    synchronisation of a Gram route: the device has nothing queued
    behind it until the caller has chosen. `explicit` is the caller
    whom an option sent here: a broken factor raises SlateError."""
    from ..core.matrix import HermitianMatrix
    r = A.resolve()
    a = r.to_dense()
    with span("gels::gram"):
        gram = jnp.matmul(jnp.conj(a.T), a,
                          precision=jax.lax.Precision.HIGHEST)
    with span("gels::potrf"):
        R = potrf(HermitianMatrix(Uplo.Upper, gram, mb=r.nb), opts)
    if isinstance(gram, jax.core.Tracer):
        return R, None
    with span("gels::select"):
        cond = float(_gram_factor_cond(gram, R.resolve().data))
    if explicit:
        obs_events.note(gram_cond=cond)
        if cond == float("inf"):
            raise SlateError(
                "cholqr: A^H A is not numerically positive definite "
                "(cond(A)^2 eps >= 1), so its Cholesky factor is NaN; "
                "use MethodGels.QR, or leave Option.MethodGels unset "
                "and gels chooses by what it observes")
    return R, cond


def cholqr(A: TiledMatrix, opts: OptionsLike = None
           ) -> Tuple[TiledMatrix, TiledMatrix]:
    """Cholesky QR: R = chol(A^H A), Q = A R^-1 (reference
    src/cholqr.cc). Squares the condition number: raises SlateError
    when the Gram matrix is not numerically positive definite
    (cond(A) >= 1/sqrt(eps)), where the reference returns info; under
    a jit trace nothing can be observed and the factors come back as
    computed."""
    from ..utils.trace import phases
    span = phases(opts)
    R = _gram_factor(A, opts, span, explicit=True)[0]
    return _cholqr_q(A, R, opts, span), R


def _cholqr_q(A: TiledMatrix, R: TiledMatrix, opts: OptionsLike, span
              ) -> TiledMatrix:
    with span("gels::apply"):
        return trsm(Side.Right, 1.0, R, dataclasses.replace(
            A.resolve(), mtype=MatrixType.General), opts)


def _cholqr_solve(A: TiledMatrix, R: TiledMatrix, B: TiledMatrix,
                  opts: OptionsLike, span) -> TiledMatrix:
    """X = R^-1 (Q^H B) with Q = A R^-1, then one step of refinement
    on the residual through the same factors (the corrected
    semi-normal equations, Bjorck 1987). Q is orthonormal only to the
    Gram matrix's own rounding, which at 65536 rows on the TPU is
    1e-5, not eps (a sum of m six-pass products): without the step
    the well-conditioned answer read 1.3e-5 from the f64 solution
    where Householder QR reads 1.5e-6 (PERF.md, PR 31). The step
    costs one pass over A and one over Q."""
    HI = jax.lax.Precision.HIGHEST
    q = _cholqr_q(A, R, opts, span).to_dense()
    a, b = A.to_dense(), B.to_dense()

    def solve(rhs):
        with span("gels::apply"):
            qtb = jnp.matmul(jnp.conj(q.T), rhs, precision=HI)
        with span("gels::trsm"):
            return trsm(Side.Left, 1.0, R, TiledMatrix.from_dense(
                qtb, B.mb, B.nb), opts).to_dense()

    x = solve(b)
    with span("gels::refine"):
        resid = b - jnp.matmul(a, x, precision=HI)
    return TiledMatrix.from_dense(x + solve(resid), B.mb, B.nb)


@instrument_driver("gels")
def gels(A: TiledMatrix, B: TiledMatrix, opts: OptionsLike = None
         ) -> TiledMatrix:
    """Least squares / minimum-norm solve (reference src/gels.cc:99,
    router over MethodGels qr|cholqr; slate.hh:932).

    m >= n: minimize ||A x - b||. With Option.MethodGels unset the
    answer is QR-grade (an error of order cond(A) eps) at every
    conditioning: a tall single-device problem forms the Gram matrix
    and its Cholesky factor, observes whether that factor exists and
    what condition number it shows (_gram_factor_cond), and keeps
    CholQR only where the normal equations lose no more than
    Householder QR would (MethodGels.select); otherwise the Gram
    factor is abandoned (counter `gels.refactors`) and blocked
    Householder QR runs. An explicit MethodGels.CholQR means what it
    says and raises SlateError where the Gram matrix cannot be
    factored. m < n: minimum-norm solution via LQ."""
    m, n = A.shape
    obs_metrics.inc("gels.solves")
    if m >= n:
        method = get_option(opts, Option.MethodGels, None)
        grid = get_option(opts, Option.Grid, None)
        if method is None or method is MethodGels.Auto:
            if grid is None and MethodGels.tall(m, n) \
                    and not isinstance(A.data, jax.core.Tracer):
                return _gels_observed(A, B, opts)
            method = MethodGels.select(m, n, on_grid=grid is not None)
        obs_events.note(method=method.value, chosen="by option or shape")
        if method is MethodGels.CholQR:
            return gels_cholqr(A, B, opts)
        if method is MethodGels.TSQR:
            return gels_tsqr(A, B, opts)
        return gels_qr(A, B, opts)
    obs_events.note(method="lq")
    # underdetermined: A = L Q, x = Q^H L^-1 b
    F = gelqf(A, opts)
    L = dataclasses.replace(F.LQ.resolve(), mtype=MatrixType.Triangular,
                            uplo=Uplo.Lower, diag=Diag.NonUnit)
    Lsq = L.slice(0, m - 1, 0, m - 1)
    Y = trsm(Side.Left, 1.0, Lsq, B, opts)
    y = Y.to_dense()
    ypad = jnp.zeros((n, y.shape[1]), y.dtype).at[:m].set(y)
    X = unmlq(Side.Left, F, TiledMatrix.from_dense(ypad, B.mb, B.nb),
              trans=True, opts=opts)
    return X


def _gels_observed(A: TiledMatrix, B: TiledMatrix,
                   opts: OptionsLike) -> TiledMatrix:
    """gels' Auto route for a tall problem on one device: CholQR's
    first two stages, then the choice from what they show."""
    from ..utils.trace import phases
    span = phases(opts)
    R, cond = _gram_factor(A, opts, span)
    method = MethodGels.select(*A.shape, gram_cond=cond)
    obs_events.note(method=method.value, chosen="observed",
                    gram_cond=cond)
    if method is MethodGels.CholQR:
        return _cholqr_solve(A, R, B, opts, span)
    obs_metrics.inc("gels.refactors")
    return gels_qr(A, B, opts)


def gels_qr(A: TiledMatrix, B: TiledMatrix,
            opts: OptionsLike = None) -> TiledMatrix:
    """Reference slate.hh:917."""
    from ..utils.trace import phases
    span = phases(opts)
    m, n = A.shape
    with span("gels::geqrf"):
        F = geqrf(A, opts)
    with span("gels::unmqr"):
        form, nt = _unmqr_form(F)
        obs_events.note(unmqr=form, unmqr_nt=nt)
        QtB = unmqr(Side.Left, F, B, trans=True, opts=opts)
    R = dataclasses.replace(F.QR.resolve(), mtype=MatrixType.Triangular,
                            uplo=Uplo.Upper, diag=Diag.NonUnit)
    Rsq = R.slice(0, n - 1, 0, n - 1)
    qtb = QtB.to_dense()[:n]
    with span("gels::trsm"):
        return trsm(Side.Left, 1.0, Rsq,
                    TiledMatrix.from_dense(qtb, B.mb, B.nb), opts)


@instrument_driver("gels_tsqr")
def gels_tsqr(A: TiledMatrix, B: TiledMatrix,
              opts: OptionsLike = None) -> TiledMatrix:
    """Least squares by communication-avoiding tree QR (reference
    ttqrt tree inside geqrf, geqrf.cc:161; the whole tall-skinny
    factorization is the tree). Q stays IMPLICIT in both routes.

    Under Option.Grid the tree is CROSS-DEVICE (dist/tsqr.py mesh
    TSQR): each device chunk-QRs its own rows and the Q^H B panels
    ride the same ppermute exchanges as the R combines — the
    reference's explicitly scheduled ttqrt/ttmqt pair, visible as
    collective-permutes in the compiled HLO (tested like the SUMMA
    schedule). Single-device (or a too-square mesh chunk) keeps the
    batched vmap tree (linalg/ca.tsqr_factors / tsqr_qt_apply), which
    never materializes the (m, n) orthogonal factor either."""
    from ..core.matrix import TriangularMatrix
    from ..utils.trace import phases
    ph = phases(opts)
    n = A.shape[1]
    r = A.resolve()
    a = A.to_dense()
    grid = get_option(opts, Option.Grid, None)
    if grid is not None:
        from ..dist import tsqr as dtsqr
        if dtsqr.eligible(grid, a.shape):
            with ph("gels_tsqr::tsqr_qt"):
                R, qtb = dtsqr.tsqr_qt(grid, a, B.to_dense(),
                                       opts=opts)
            Rt = TriangularMatrix(Uplo.Upper, R, mb=r.nb)
            with ph("gels_tsqr::trsm"):
                return trsm(Side.Left, 1.0, Rt,
                            TiledMatrix.from_dense(qtb, B.mb, B.nb),
                            opts)
    from .ca import tsqr_factors, tsqr_qt_apply
    with ph("gels_tsqr::tree"):
        qs, R = tsqr_factors(a, chunk=max(r.mb, 4 * n))
        qtb = tsqr_qt_apply(qs, B.to_dense(), a.shape[0])
    Rt = TriangularMatrix(Uplo.Upper, R, mb=r.nb)
    with ph("gels_tsqr::trsm"):
        return trsm(Side.Left, 1.0, Rt,
                    TiledMatrix.from_dense(qtb, B.mb, B.nb), opts)


def gels_cholqr(A: TiledMatrix, B: TiledMatrix,
                opts: OptionsLike = None) -> TiledMatrix:
    """Reference slate.hh:924 / src/gels_cholqr.cc: the normal
    equations through CholQR, for well-conditioned A (an error of
    order cond(A)^2 eps). Raises SlateError where the Gram matrix
    cannot be factored (see cholqr) instead of returning NaN."""
    from ..utils.trace import phases
    span = phases(opts)
    R = _gram_factor(A, opts, span, explicit=True)[0]
    return _cholqr_solve(A, R, B, opts, span)
