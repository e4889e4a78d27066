"""Parallel BLAS-3 drivers (reference src/gemm.cc, hemm, symm, trmm,
trsm, herk, syrk, her2k, syr2k, gbmm, hbmm, tbsm — slate.hh:181-457).

TPU-native design: the reference implements SUMMA-style rank-k loops with
explicit tile broadcasts (gemmC.cc:84-117) and per-device batched BLAS;
here each driver is one dense XLA op on the logical matrix. Under a
NamedSharding'ed input, XLA SPMD inserts exactly the all-gather /
reduce-scatter pattern SUMMA hand-codes — on TPU the collectives ride ICI.
Structure (triangular/symmetric/Hermitian/band) is applied as fused masks
by ``to_dense``; results are written back into the output's tiled padded
storage.

Method variants (gemmA/gemmC, trsmA/trsmB — reference method.hh) select
*which operand is broadcast*; that choice is XLA's under SPMD, so the
variants are accepted and recorded but compile to the same program.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..core.enums import Diag, MatrixType, Side, Uplo
from ..core.exceptions import DimensionError, slate_assert
from ..core.options import OptionsLike
from ..core.tiles import TiledMatrix


def _logical(A: TiledMatrix) -> jax.Array:
    return A.to_dense()


def _store(C: TiledMatrix, new_logical) -> TiledMatrix:
    """Write a logical (m, n) result back into C's padded tiled storage."""
    r = C.resolve()
    mp, np_ = r.data.shape
    data = jnp.pad(new_logical.astype(r.dtype),
                   ((0, mp - r.shape[0]), (0, np_ - r.shape[1])))
    return dataclasses.replace(r, data=data)


def _dot(a, b, precision):
    return jnp.matmul(a, b, precision=precision)


# -- general / band matrix multiply ---------------------------------------

def gemm(alpha, A: TiledMatrix, B: TiledMatrix, beta, C: TiledMatrix,
         opts: OptionsLike = None, precision=jax.lax.Precision.HIGHEST
         ) -> TiledMatrix:
    """C := alpha op(A) op(B) + beta C (reference src/gemm.cc:72,
    slate.hh:190). Transposition travels on the A/B view flags."""
    m, k = A.shape
    k2, n = B.shape
    if k != k2 or C.shape != (m, n):
        raise DimensionError(
            f"gemm: {A.shape} x {B.shape} -> {C.shape}")
    from ..core.methods import MethodGemm
    from ..core.options import Option, get_option
    method = get_option(opts, Option.MethodGemm, MethodGemm.Auto)
    grid = get_option(opts, Option.Grid, None)
    if method is MethodGemm.Auto and grid is not None:
        # measured routing: a tune-cache entry can promote Auto to the
        # hand-scheduled SUMMA on meshes where it beat the SPMD
        # partitioner; cold cache keeps today's Auto (partitioner) path
        from ..tune.select import tuned_method
        cached = tuned_method("gemm", "gemm", opts=opts,
                              option=Option.MethodGemm,
                              n=min(m, n), dtype=C.dtype)
        if cached is MethodGemm.Summa:
            method = cached
    if method is MethodGemm.Summa and grid is not None:
        # explicit-communication path: hand-scheduled SUMMA over the
        # mesh (reference gemmC.cc broadcast loop) instead of the SPMD
        # partitioner's choice
        from ..core.tiles import round_up
        from ..parallel.collectives import summa_gemm
        a, b = _logical(A), _logical(B)
        p, q = grid.p, grid.q
        # pad m/p and n/q only; summa_gemm owns the ragged-k padding
        mp, np_ = round_up(m, p * q), round_up(n, p * q)
        ap = jnp.pad(a, ((0, mp - m), (0, 0)))
        bp = jnp.pad(b, ((0, 0), (0, np_ - n)))
        prod = summa_gemm(grid, ap, bp, precision=precision)[:m, :n]
        return _store(C, jnp.asarray(alpha) * prod
                      + jnp.asarray(beta) * _logical(C))
    c = jnp.asarray(alpha) * _dot(_logical(A), _logical(B), precision) \
        + jnp.asarray(beta) * _logical(C)
    return _store(C, c)


def gbmm(alpha, A: TiledMatrix, B: TiledMatrix, beta, C: TiledMatrix,
         opts: OptionsLike = None) -> TiledMatrix:
    """Band A times general B (reference src/gbmm.cc:1-326, slate.hh:181).
    Narrow bands run the real windowed product (band.band_mm: one
    batched MXU matmul over block-row windows, O(m*(kl+ku+nb)*p) FLOPs
    — the reference's in-band-tiles-only iteration); wide bands fall
    back to dense gemm."""
    from ..core.enums import Op
    from ..core.methods import MethodGemm
    from ..core.options import Option, get_option
    from .band import band_is_narrow, band_mm
    m, k = A.shape
    if B.shape[0] != k or C.shape != (m, B.shape[1]):
        raise DimensionError(
            f"gbmm: {A.shape} x {B.shape} -> {C.shape}")
    # route on metadata only (resolve materializes the transpose);
    # transposed views swap kl/ku and mb/nb
    if A.op is Op.NoTrans:
        kl, ku, nbE = A.kl, A.ku, A.nb
    else:
        kl, ku, nbE = A.ku, A.kl, A.mb
    summa = (get_option(opts, Option.MethodGemm, MethodGemm.Auto)
             is MethodGemm.Summa)
    if A.mtype is MatrixType.GeneralBand and kl >= 0 and ku >= 0 \
            and not summa \
            and band_is_narrow(min(A.shape), nbE, max(kl, ku)):
        r = A.resolve()
        prod = band_mm(r.to_dense(), r.kl, r.ku, B.to_dense(), r.nb)
        return _store(C, jnp.asarray(alpha) * prod
                      + jnp.asarray(beta) * _logical(C))
    return gemm(alpha, A, B, beta, C, opts)


def hbmm(side: Side, alpha, A: TiledMatrix, B: TiledMatrix, beta,
         C: TiledMatrix, opts: OptionsLike = None) -> TiledMatrix:
    """Hermitian-band A (reference src/hbmm.cc, slate.hh:217). Narrow
    bands run the windowed product on the symmetrized band (to_dense
    applies the Hermitian structure), kl = ku = kd; the Right side
    reuses the Left kernel through C = (A^H B^H)^H with A^H = A."""
    from .band import band_is_narrow, band_mm
    n = A.shape[0]
    bm, bn = B.shape
    if (bm if side is Side.Left else bn) != n or C.shape != B.shape:
        raise DimensionError(
            f"hbmm: {side} {A.shape} x {B.shape} -> {C.shape}")
    from ..core.enums import Op
    kd = max(A.kl, A.ku)
    nbE = A.nb if A.op is Op.NoTrans else A.mb
    # kl/ku == -1 sentinels mean "full bandwidth": fall back to hemm
    if A.mtype is MatrixType.HermitianBand and A.kl >= 0 and A.ku >= 0 \
            and band_is_narrow(min(A.shape), nbE, kd):
        r = A.resolve()
        a = r.to_dense()                    # full Hermitian band
        b = B.to_dense()
        if side is Side.Left:
            prod = band_mm(a, kd, kd, b, r.nb)
        else:
            prod = jnp.conj(band_mm(a, kd, kd, jnp.conj(b.T),
                                    r.nb)).T
        return _store(C, jnp.asarray(alpha) * prod
                      + jnp.asarray(beta) * _logical(C))
    return hemm(side, alpha, A, B, beta, C, opts)


# -- symmetric / Hermitian multiply ---------------------------------------

def _sided_mm(side: Side, alpha, A, B, beta, C, precision):
    a, b, c = _logical(A), _logical(B), _logical(C)
    if side is Side.Left:
        prod = _dot(a, b, precision)
    else:
        prod = _dot(b, a, precision)
    return _store(C, jnp.asarray(alpha) * prod + jnp.asarray(beta) * c)


def hemm(side: Side, alpha, A: TiledMatrix, B: TiledMatrix, beta,
         C: TiledMatrix, opts: OptionsLike = None,
         precision=jax.lax.Precision.HIGHEST) -> TiledMatrix:
    """C := alpha A B + beta C with A Hermitian (reference src/hemm.cc,
    slate.hh:227; method variants hemmA/hemmC method.hh:132)."""
    return _sided_mm(side, alpha, A, B, beta, C, precision)


def symm(side: Side, alpha, A: TiledMatrix, B: TiledMatrix, beta,
         C: TiledMatrix, opts: OptionsLike = None,
         precision=jax.lax.Precision.HIGHEST) -> TiledMatrix:
    """Reference slate.hh:272."""
    return _sided_mm(side, alpha, A, B, beta, C, precision)


# -- triangular multiply / solve ------------------------------------------

def trmm(side: Side, alpha, A: TiledMatrix, B: TiledMatrix,
         opts: OptionsLike = None,
         precision=jax.lax.Precision.HIGHEST) -> TiledMatrix:
    """B := alpha op(A) B (Left) or alpha B op(A) (Right); A triangular
    (reference src/trmm.cc, slate.hh:297)."""
    a, b = _logical(A), _logical(B)
    prod = _dot(a, b, precision) if side is Side.Left \
        else _dot(b, a, precision)
    return _store(B, jnp.asarray(alpha) * prod)


def trsm(side: Side, alpha, A: TiledMatrix, B: TiledMatrix,
         opts: OptionsLike = None) -> TiledMatrix:
    """Solve op(A) X = alpha B (Left) or X op(A) = alpha B (Right);
    A triangular (reference src/trsm.cc via work::trsm pipeline,
    work_trsm.cc:53).

    TPU-native: XLA TriangularSolve lowers to a blocked
    invert-diagonal-then-matmul scheme — the same math as the reference's
    forward sweep of tile trsm + gemm updates, chosen by the compiler.
    The reference's lookahead pipelining (work_trsm.cc:70-110) corresponds
    to XLA's async scheduling of the per-block matmuls."""
    from ..core.options import Option, get_option
    from .blocked import trsm_dense
    ra = A.resolve()
    lower = ra.uplo is Uplo.Lower
    grid = get_option(opts, Option.Grid, None)
    if grid is not None and ra.mtype is MatrixType.Triangular \
            and ra.diag is Diag.NonUnit:
        # the grid's block loops read the stored triangle and nothing
        # else: no masked copy of the whole matrix on every device
        a = ra.data[:ra.m, :ra.n]
    else:
        # to_dense applies the triangle/band masks and bakes Diag.Unit
        # ones onto the diagonal, so the solve sees the logical matrix
        a = ra.to_dense()
    b = _logical(B)
    x = trsm_dense(a, jnp.asarray(alpha, b.dtype) * b,
                   left=(side is Side.Left), lower=lower, nb=ra.nb,
                   grid=grid)
    return _store(B, x)


def tbsm(side: Side, alpha, A: TiledMatrix, B: TiledMatrix,
         pivots=None, opts: OptionsLike = None) -> TiledMatrix:
    """Triangular-band solve (reference src/tbsm.cc, slate.hh:306), with
    optional pivots from gbtrf. Narrow bands use the O(n*kd*nrhs)
    windowed sweeps (linalg/band.py).

    `pivots` accepts either a raw swap vector (dense getrf convention:
    global swaps, applied as one gather up front) or the LUFactors from
    the windowed band gbtrf — those carry block-local pivots that are
    only correct interleaved with the elimination, so tbsm replays the
    gbtrs forward sweep for them (passing `F.pivots` raw would be
    silently wrong whenever a pivot crosses a block boundary)."""
    from .band import band_is_narrow, band_width_of
    if pivots is not None and getattr(pivots, "band", False):
        F = pivots
        ra = A.resolve()
        if side is Side.Left and ra.uplo is Uplo.Lower:
            from .band import gb_forward_solve
            rf = F.LU.resolve()
            b = jnp.asarray(alpha, B.dtype) * B.to_dense()
            x = gb_forward_solve(rf.data, F.pivots, b, rf.n, rf.nb,
                                 rf.kl)
            return _store(B, x)
        # upper factor of a band LU needs no pivots
        pivots = None
    elif pivots is not None:
        from .lu import apply_pivots
        B = apply_pivots(pivots, B)
        pivots = None
    ra = A.resolve()
    width = band_width_of(ra)
    narrow = band_is_narrow(ra.n, ra.nb, width)
    if side is Side.Left and ra.mtype is MatrixType.TriangularBand \
            and narrow:
        from .band import band_trsm_lower, band_trsm_upper
        b = jnp.asarray(alpha, B.dtype) * B.to_dense()
        a = ra.to_dense()
        if ra.uplo is Uplo.Lower:
            x = band_trsm_lower(a, b, ra.n, ra.nb, width,
                                unit_diagonal=False)
        else:
            x = band_trsm_upper(a, b, ra.n, ra.nb, width)
        return _store(B, x)
    return trsm(side, alpha, A, B, opts)


# -- rank-k / rank-2k updates ---------------------------------------------

def herk(alpha, A: TiledMatrix, beta, C: TiledMatrix,
         opts: OptionsLike = None,
         precision=jax.lax.Precision.HIGHEST) -> TiledMatrix:
    """C := alpha op(A) op(A)^H + beta C, C Hermitian (reference
    src/herk.cc, slate.hh:363). alpha/beta real."""
    slate_assert(C.mtype in (MatrixType.Hermitian, MatrixType.Symmetric),
                 "herk: C must be Hermitian")
    a = _logical(A)
    c = _logical(C)
    prod = _dot(a, jnp.conj(a.T), precision)
    return _store(C, jnp.asarray(alpha) * prod + jnp.asarray(beta) * c)


def syrk(alpha, A: TiledMatrix, beta, C: TiledMatrix,
         opts: OptionsLike = None,
         precision=jax.lax.Precision.HIGHEST) -> TiledMatrix:
    """C := alpha op(A) op(A)^T + beta C, C symmetric (slate.hh:384)."""
    a = _logical(A)
    c = _logical(C)
    prod = _dot(a, a.T, precision)
    return _store(C, jnp.asarray(alpha) * prod + jnp.asarray(beta) * c)


def her2k(alpha, A: TiledMatrix, B: TiledMatrix, beta, C: TiledMatrix,
          opts: OptionsLike = None,
          precision=jax.lax.Precision.HIGHEST) -> TiledMatrix:
    """C := alpha A B^H + conj(alpha) B A^H + beta C (slate.hh:405)."""
    a, b, c = _logical(A), _logical(B), _logical(C)
    prod = jnp.asarray(alpha) * _dot(a, jnp.conj(b.T), precision)
    prod = prod + jnp.conj(jnp.asarray(alpha)) * _dot(b, jnp.conj(a.T),
                                                      precision)
    return _store(C, prod + jnp.asarray(beta) * c)


def syr2k(alpha, A: TiledMatrix, B: TiledMatrix, beta, C: TiledMatrix,
          opts: OptionsLike = None,
          precision=jax.lax.Precision.HIGHEST) -> TiledMatrix:
    """C := alpha (A B^T + B A^T) + beta C (slate.hh:436)."""
    a, b, c = _logical(A), _logical(B), _logical(C)
    prod = _dot(a, b.T, precision) + _dot(b, a.T, precision)
    return _store(C, jnp.asarray(alpha) * prod + jnp.asarray(beta) * c)


def gemmA(alpha, A, B, beta, C, opts=None, **kw):
    """gemmA variant (reference src/gemmA.cc — keeps C traffic low for
    few columns; under SPMD the partitioner makes this scheduling
    choice, so both variants compile to the same program)."""
    return gemm(alpha, A, B, beta, C, opts, **kw)


def gemmC(alpha, A, B, beta, C, opts=None, **kw):
    """gemmC variant (reference src/gemmC.cc)."""
    return gemm(alpha, A, B, beta, C, opts, **kw)


def trsmA(side, alpha, A, B, opts=None):
    """trsmA variant (reference src/trsmA.cc — broadcasts B to A's
    ranks; scheduling is XLA's under SPMD)."""
    return trsm(side, alpha, A, B, opts)


def trsmB(side, alpha, A, B, opts=None):
    """trsmB variant (reference src/trsmB.cc)."""
    return trsm(side, alpha, A, B, opts)
