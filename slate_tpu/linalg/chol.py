"""Cholesky family (reference src/potrf.cc, posv.cc, potrs.cc, potri.cc,
trtri.cc, trtrm.cc, pbtrf/pbtrs/pbsv; SURVEY §3.1).

TPU-native blocked right-looking Cholesky: the reference's OpenMP task DAG
(panel potrf -> column bcast -> trsm -> lookahead herk trailing updates,
potrf.cc:85-192) becomes a statically-unrolled blocked loop under jit —
each step is a diagonal-block factor (MXU-small), a panel triangular
solve, and one large trailing herk. XLA's scheduler overlaps the panel
chain with trailing updates exactly where the reference uses
Option::Lookahead; under a sharded input SPMD inserts the column
broadcasts the reference hand-codes as tileBcast.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ..core.enums import Diag, MatrixType, Side, Uplo
from ..core.exceptions import slate_assert
from ..core.methods import MethodFactor
from ..core.options import (Option, OptionsLike, get_option,
                            get_option_tuned)
from ..core.tiles import TiledMatrix, ceil_div, pad_diag_identity
from ..obs import events as obs_events
from ..obs import metrics as obs_metrics
from ..obs.events import instrument_driver
from .blas3 import trsm


def _chol_blocked(a: jax.Array, nb: int,
                  precision=jax.lax.Precision.HIGHEST,
                  grid=None, lookahead: int = 1) -> jax.Array:
    """Lower Cholesky of a padded (N, N) Hermitian array whose padded
    diagonal is identity (reference impl::potrf task DAG, potrf.cc:85-192
    — statically unrolled; panels via invert-then-matmul, see
    blocked.py). With a grid, block steps carry sharding constraints;
    lookahead selects the software-pipelined loop (blocked.py)."""
    from .blocked import cholesky_blocked
    return cholesky_blocked(a, nb, precision=precision, grid=grid,
                            lookahead=lookahead)


def _potrf_prep(A: TiledMatrix, r: TiledMatrix, nb: int,
                fast: bool) -> jax.Array:
    """The (np_, np_) array potrf factors, np_ a multiple of nb with an
    identity on the padded diagonal: A's stored triangle as it is
    (`fast`; transposed for Upper), or its mirrored logical matrix."""
    if fast:
        a = r.data if r.uplo is Uplo.Lower else jnp.conj(r.data.T)
        return pad_diag_identity(a, r.n, r.n)
    np_ = ceil_div(max(r.n, 1), nb) * nb
    full = A.to_dense()                  # mirrored logical matrix
    a = jnp.pad(full, ((0, np_ - r.m), (0, np_ - r.n)))
    return pad_diag_identity(a, r.m, r.n)


@functools.lru_cache(maxsize=None)
def _grid_potrf_programs(grid):
    """`_potrf_prep` and `_chol_blocked` as compiled programs whose
    results stay on `grid`; jit keys them by the matrix's shape, dtype
    and structure and by the blocking."""
    from ..parallel.sharding import constrain

    def prep(A, r, nb, fast):
        return constrain(_potrf_prep(A, r, nb, fast), grid)

    def tiled(a, nb, lookahead):
        return constrain(_chol_blocked(a, nb, grid=grid,
                                       lookahead=lookahead), grid)

    return (jax.jit(prep, static_argnums=(2, 3)),
            jax.jit(tiled, static_argnums=(1,),
                    static_argnames=("lookahead",)))


def _count_block_steps(n: int, nb: int, steps: int, grid) -> str:
    """A scan form of `steps` block steps over an order-n operand is
    being dispatched under `grid`: count them by how the form reaches
    its blocks (`blocked.grid_blocks`; the helpers themselves run at
    trace time only) and return that."""
    from .blocked import grid_blocks
    blocks = grid_blocks(n, nb, grid)
    if blocks == "local":
        obs_metrics.inc("grid.block_steps_local", steps)
    else:
        obs_metrics.inc("grid.block_steps_masked", steps)
    return blocks


@functools.lru_cache(maxsize=None)
def _grid_potrs_program(grid):
    """`potrs` on `grid` as one compiled program (two sweeps of some
    hundreds of block steps between them when dispatched eagerly)."""
    return jax.jit(lambda A, B: _potrs(A, B, {Option.Grid: grid}))


@instrument_driver("potrf")
def potrf(A: TiledMatrix, opts: OptionsLike = None,
          return_info: bool = False):
    """Cholesky factor A = L L^H (or U^H U); returns a TriangularMatrix
    with A's uplo (reference src/potrf.cc:262, in-place semantics made
    functional).

    With return_info=True returns (L, info): info == 0 on success,
    info == k > 0 if the leading minor of order k is not positive
    definite (reference potrf.cc:208 reduce_info; here the diagonal
    scan reduces over the mesh under SPMD).

    Routing altitude: this driver factors DEVICE-RESIDENT matrices
    (HBM-bounded). Beyond-HBM host-resident problems take
    ooc.potrf_ooc — single-device streamed, or 2D-block-cyclic
    sharded over a mesh via its ``grid=`` route (MethodOOC
    arbitration, dist/shard_ooc.py)."""
    slate_assert(A.mtype in (MatrixType.Hermitian, MatrixType.Symmetric,
                             MatrixType.HermitianBand),
                 "potrf: A must be Hermitian/symmetric")
    r = A.uniform().resolve()    # non-uniform tiles re-tile at entry
    nb = r.nb
    grid = get_option(opts, Option.Grid, None)
    method = get_option(opts, Option.MethodFactor, MethodFactor.Auto)
    if method is MethodFactor.Auto:
        if grid is not None:
            method = MethodFactor.Tiled
        else:
            # measured Fused/Tiled routing from the tune cache when
            # present; the frozen Auto heuristic otherwise
            from ..tune.select import tuned_method
            cached = tuned_method("potrf", "factor", opts=opts,
                                  option=Option.MethodFactor,
                                  n=r.n, dtype=r.dtype)
            method = cached if cached is not None \
                and cached is not MethodFactor.Auto \
                else MethodFactor.select(r.data)
    # square padded storage, multiple of nb; output uses mb = nb so the
    # factor's tile geometry is self-consistent even if input mb != nb
    np_ = ceil_div(max(r.n, 1), nb) * nb
    # fast prep: neither XLA's kernel nor the blocked loops read past
    # the stored triangle, so skip the Hermitian mirror (a transpose
    # pass over the whole matrix; across a mesh an all-to-all and a
    # second copy of it) and hand the raw padded storage — lower for
    # Lower, transposed storage for Upper — straight to the
    # factorization
    fast = (not return_info and r.data.shape == (np_, np_)
            and r.mb == nb and A.mtype is not MatrixType.HermitianBand)
    lookahead = None
    if return_info or method is not MethodFactor.Fused:
        lookahead = get_option_tuned(opts, Option.Lookahead, "potrf",
                                     n=r.n, dtype=r.dtype)
    if obs_events.enabled():
        from .blocked import (chol_form, chol_scan_stages,
                              chol_scan_update_flops)
        form = ("native" if method is MethodFactor.Fused
                and not return_info
                else chol_form(np_, nb, guarded=return_info))
        # static slices but in a scan form under a grid; the scan form
        # runs in stages, whose update FLOPs are counted under a grid
        # beside the n^3/3 a Cholesky needs
        blocks, stages = "slice", 0
        if form == "scan":
            stages = len(chol_scan_stages(np_, nb, grid))
            if grid is not None:
                blocks = _count_block_steps(np_, nb, np_ // nb, grid)
                obs_metrics.inc("grid.update_flops",
                                chol_scan_update_flops(np_, nb, grid))
                obs_metrics.inc("grid.update_flops_needed", np_ ** 3 // 3)
        obs_events.note(
            factor=method.value, nb=nb, nt=np_ // nb, form=form,
            blocks=blocks, stages=stages,
            grid="1x1" if grid is None else "%dx%d" % (grid.p, grid.q))
    if grid is not None and not return_info:
        # across a mesh the prep and the factorization are one compiled
        # program each: dispatched eagerly, every mask and slice of
        # the steps is an array and a launch of its own, and some are
        # whole matrices on one device
        prep, tiled = _grid_potrf_programs(grid)
    else:
        prep, tiled = _potrf_prep, functools.partial(_chol_blocked,
                                                     grid=grid)
    with obs_events.span("posv::prep", cat="step"):
        if fast and r.uplo is Uplo.Lower and np_ == r.n:
            a = r.data      # read as stored: nothing to prepare
        else:
            a = prep(A, r, nb, fast)
    info = None
    with obs_events.span("posv::factor", cat="step"):
        if return_info:
            # guarded tiled path: survives non-SPD input and reports
            # the exact first failed leading-minor index (XLA's native
            # cholesky NaNs the whole output on CPU, so its NaN pattern
            # cannot reconstruct LAPACK's info)
            from .info import cholesky_blocked_info
            L, info = cholesky_blocked_info(a, nb, grid,
                                            lookahead=lookahead)
        elif method is MethodFactor.Fused:
            # single fused XLA program — the fastest single-device path
            # (the reference's Target::Devices switch,
            # potrf.cc:262-277); symmetrize_input=False skips a
            # whole-matrix transpose pass (the kernel reads only the
            # lower triangle, like LAPACK potrf)
            L = jax.lax.linalg.cholesky(a, symmetrize_input=False)
        else:
            L = tiled(a, nb, lookahead=lookahead)
    if r.uplo is Uplo.Upper:
        data = jnp.conj(L.T)
    else:
        data = L
    kl = r.kl if A.mtype is MatrixType.HermitianBand else -1
    ku = r.ku if A.mtype is MatrixType.HermitianBand else -1
    mtype = (MatrixType.TriangularBand
             if A.mtype is MatrixType.HermitianBand
             else MatrixType.Triangular)
    out = dataclasses.replace(r, data=data, mb=nb, nb=nb, mtype=mtype,
                              diag=Diag.NonUnit, kl=kl, ku=ku)
    if return_info:
        return out, info
    return out


@instrument_driver("potrs")
def potrs(A: TiledMatrix, B: TiledMatrix,
          opts: OptionsLike = None) -> TiledMatrix:
    """Solve using the factor from potrf (reference src/potrs.cc:75-77:
    two triangular solves). Under Option.Grid the two are one compiled
    program; `trsm` reads no other option."""
    grid = get_option(opts, Option.Grid, None)
    if grid is not None:
        if obs_events.enabled():
            from .blocked import trsm_form
            r = A.resolve()
            if trsm_form(r.n, r.nb) == "scan":
                # the two sweeps of `_potrs`, r.n / r.nb steps each
                _count_block_steps(r.n, r.nb, 2 * (r.n // r.nb), grid)
        return _grid_potrs_program(grid)(A, B)
    return _potrs(A, B, opts)


def _potrs(A: TiledMatrix, B: TiledMatrix,
           opts: OptionsLike = None) -> TiledMatrix:
    if A.uplo is Uplo.Lower:
        X = trsm(Side.Left, 1.0, A, B, opts)            # L y = b
        X = trsm(Side.Left, 1.0, A.conj_transpose(), X, opts)  # L^H x = y
    else:
        X = trsm(Side.Left, 1.0, A.conj_transpose(), B, opts)  # U^H y = b
        X = trsm(Side.Left, 1.0, A, X, opts)            # U x = y
    return X


@instrument_driver("posv")
def posv(A: TiledMatrix, B: TiledMatrix, opts: OptionsLike = None,
         return_info: bool = False):
    """Solve A X = B, A Hermitian positive definite (reference
    src/posv.cc:83-91). Returns (factor, X), or (factor, X, info)
    with return_info=True (info as in potrf). When info > 0 the solve
    is skipped (reference posv semantics) and X is NaN-filled."""
    from ..utils.trace import phases
    ph = phases(opts)
    if return_info:
        with ph("posv::potrf"):
            L, info = potrf(A, opts, return_info=True)
        meta = jax.eval_shape(lambda: potrs(L, B, opts))
        with ph("posv::potrs"):
            data = jax.lax.cond(
                info == 0,
                lambda: potrs(L, B, opts).data,
                lambda: jnp.full(meta.data.shape, jnp.nan,
                                 meta.data.dtype))
        return L, dataclasses.replace(meta, data=data), info
    with ph("posv::potrf"):
        L = potrf(A, opts)
    with ph("posv::potrs"), obs_events.span("posv::solve", cat="step"):
        X = potrs(L, B, opts)
    return L, X


def trtri(A: TiledMatrix, opts: OptionsLike = None) -> TiledMatrix:
    """Triangular inverse (reference src/trtri.cc, slate.hh:349)."""
    r = A.resolve()
    a = r.to_dense()
    from ..core.tiles import round_up
    from .blocked import invert_triangular
    n = a.shape[0]
    npd = round_up(max(n, 1), 128)
    if npd != n:
        # identity-pad so the Pallas/blocked inverse sees an aligned
        # block; inv of blkdiag(A, I) is blkdiag(inv(A), I)
        a = pad_diag_identity(jnp.pad(a, ((0, npd - n), (0, npd - n))),
                              n, n)
    inv = invert_triangular(a, lower=(r.uplo is Uplo.Lower),
                            unit_diagonal=(r.diag is Diag.Unit))[:n, :n]
    from .blas3 import _store
    return _store(r, inv)


def trtrm(A: TiledMatrix, opts: OptionsLike = None) -> TiledMatrix:
    """L := L^H L or U := U U^H on the triangle (reference src/trtrm.cc,
    slate.hh:356) — the second half of potri."""
    r = A.resolve()
    a = r.to_dense()
    if r.uplo is Uplo.Lower:
        prod = jnp.matmul(jnp.conj(a.T), a,
                          precision=jax.lax.Precision.HIGHEST)
    else:
        prod = jnp.matmul(a, jnp.conj(a.T),
                          precision=jax.lax.Precision.HIGHEST)
    from .blas3 import _store
    out = _store(r, prod)
    return dataclasses.replace(out, mtype=MatrixType.Hermitian,
                               diag=Diag.NonUnit)


def potri(A: TiledMatrix, opts: OptionsLike = None) -> TiledMatrix:
    """A^{-1} from the potrf factor (reference src/potri.cc, slate.hh:813:
    trtri then trtrm)."""
    Linv = trtri(A, opts)
    return trtrm(Linv, opts)


# -- band Cholesky --------------------------------------------------------

def _band_width(A: TiledMatrix) -> int:
    from .band import band_width_of
    return band_width_of(A)


def _use_band_path(A: TiledMatrix, width: int) -> bool:
    from .band import band_is_narrow
    r = A.resolve()
    return band_is_narrow(r.n, r.nb, width)


def pbtrf(A: TiledMatrix, opts: OptionsLike = None) -> TiledMatrix:
    """Band Cholesky (reference src/pbtrf.cc, slate.hh:758): the real
    O(n*kd^2) windowed band algorithm (linalg/band.py) when the band is
    narrow, the dense blocked path otherwise (the factor of a kd-band
    SPD matrix is kd-band triangular either way)."""
    kd = _band_width(A)
    if A.mtype is MatrixType.HermitianBand and _use_band_path(A, kd):
        from .band import pbtrf_band
        r = A.resolve()
        full = A.to_dense()
        np_ = ceil_div(max(r.n, 1), r.nb) * r.nb
        a = jnp.pad(full, ((0, np_ - r.m), (0, np_ - r.n)))
        a = pad_diag_identity(a, r.m, r.n)
        L = pbtrf_band(a, r.n, r.nb, kd)
        if r.uplo is Uplo.Upper:
            L = jnp.conj(L.T)
        return dataclasses.replace(
            r, data=L, mb=r.nb, nb=r.nb, mtype=MatrixType.TriangularBand,
            diag=Diag.NonUnit, kl=r.kl, ku=r.ku)
    return potrf(A, opts)


def pbtrs(A: TiledMatrix, B: TiledMatrix,
          opts: OptionsLike = None) -> TiledMatrix:
    """Band solve from the pbtrf factor (reference slate.hh:784):
    windowed band triangular solves, O(n*kd*nrhs)."""
    kd = _band_width(A)
    if A.mtype is MatrixType.TriangularBand and _use_band_path(A, kd):
        from .band import band_trsm_lower
        from .blas3 import _store
        r = A.resolve()
        l = r.to_dense() if r.uplo is Uplo.Lower \
            else jnp.conj(r.to_dense().T)
        b = B.to_dense()
        y = band_trsm_lower(l, b, r.n, r.nb, kd)
        x = band_trsm_lower(l, y, r.n, r.nb, kd, conj_trans=True)
        return _store(B, x)
    return potrs(A, B, opts)


def pbsv(A: TiledMatrix, B: TiledMatrix, opts: OptionsLike = None):
    """Reference slate.hh:665."""
    L = pbtrf(A, opts)
    return L, pbtrs(L, B, opts)


# -- mixed precision ------------------------------------------------------

def _lo_potrs(ctx, factors, rhs: jax.Array) -> jax.Array:
    """The lo solve the mixed Cholesky drivers hand `refine`'s
    programs: `potrs` on dense arrays. `factors` = (the lo factor's
    padded data, ones on its padding's diagonal,), `ctx` = (lower, nb,
    grid); rhs (n, k) in the factor's dtype, and so is the answer. The
    factor is read where it lies, its conjugate transpose too
    (`refine.tri_sweep`)."""
    from .refine import lo_work_dtype, tri_sweep
    (f,) = factors
    lower, nb, grid = ctx
    n = rhs.shape[0]
    if grid is not None:
        from .blocked import trsm_dense
        f = f[:n, :n]
        fh = jnp.conj(f.T)
        first, second = (f, fh) if lower else (fh, f)
        y = trsm_dense(first, rhs, left=True, lower=True, nb=nb, grid=grid)
        return trsm_dense(second, y, left=True, lower=False, nb=nb,
                          grid=grid)
    y = jnp.pad(rhs, ((0, f.shape[0] - n), (0, 0))).astype(
        lo_work_dtype(f.dtype))
    # lower: L y = b, L^H x = y; upper: U^H y = b, U x = y
    for adjoint in ((False, True) if lower else (True, False)):
        y = tri_sweep(f, y, lower=lower, nb=nb, adjoint=adjoint)
    return y[:n].astype(rhs.dtype)


def _mixed_factor(name: str, A: TiledMatrix, opts: OptionsLike):
    """What `posv_mixed` and `posv_mixed_gmres` share: A demoted once
    (`<name>::demote`), factored by `potrf` (`<name>::factor`).
    Returns (L, ctx, factors) for `_lo_potrs`."""
    from ..utils.trace import phases
    from .refine import demote
    A_lo = demote(name, A, opts)
    with phases(opts)(name + "::factor"):
        L = potrf(A_lo, opts)
        rl = L.resolve()
        # the padded factor's diagonal kept nonsingular, as an LU's
        # is: once here, not in every lo solve
        data = pad_diag_identity(rl.data, rl.m, rl.n)
    obs_events.note(lo=str(A_lo.dtype))
    return L, (rl.uplo is Uplo.Lower, rl.nb,
               get_option(opts, Option.Grid, None)), (data,)


@instrument_driver("posv_mixed")
def posv_mixed(A: TiledMatrix, B: TiledMatrix, opts: OptionsLike = None):
    """Mixed-precision Cholesky with iterative refinement (reference
    src/posv_mixed.cc, slate.hh:694). Returns (factor_lo, X, iters);
    iters < 0 means the full-precision fallback produced X, which the
    host decided on the refinement's verdict (`refine.py`)."""
    from .refine import iterative_refinement
    from .blas3 import _store
    L, ctx, factors = _mixed_factor("posv_mixed", A, opts)
    obs_events.note(refine="ir")

    def full_solve():
        return potrs(potrf(A, opts), B, opts).to_dense()

    x, iters = iterative_refinement(A, B, _lo_potrs, ctx, factors,
                                    full_solve, opts, name="posv_mixed")
    return L, _store(B, x), iters


@instrument_driver("posv_mixed_gmres")
def posv_mixed_gmres(A: TiledMatrix, B: TiledMatrix,
                     opts: OptionsLike = None):
    """Mixed-precision FGMRES-IR Cholesky (reference
    src/posv_mixed_gmres.cc, slate.hh:738). Single RHS."""
    from .refine import fgmres_ir
    from .blas3 import _store
    slate_assert(B.shape[1] == 1,
                 "posv_mixed_gmres supports one right-hand side")
    L, ctx, factors = _mixed_factor("posv_mixed_gmres", A, opts)
    obs_events.note(refine="fgmres")

    def full_solve():
        return potrs(potrf(A, opts), B, opts).to_dense()

    x, iters = fgmres_ir(A, B, _lo_potrs, ctx, factors, full_solve,
                         restart_cap=max(A.resolve().mb - 1, 1),
                         opts=opts, name="posv_mixed_gmres")
    return L, _store(B, x), iters
