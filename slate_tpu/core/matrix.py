"""Matrix class hierarchy (reference include/slate/*.hh, 12 classes —
SURVEY layer map row 4).

The reference's C++ hierarchy (Matrix, BaseTrapezoidMatrix →
Trapezoid/Triangular/Symmetric/Hermitian, band variants) exists primarily
to dispatch structure-aware algorithms and constrain constructors. Here the
structure lives in TiledMatrix metadata; these thin constructors give the
same vocabulary and validation. Each returns a TiledMatrix tagged with the
right MatrixType, so the whole hierarchy stays a single pytree type and
every driver accepts any of them.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from .enums import Diag, MatrixType, Uplo
from .exceptions import DimensionError
from .tiles import TiledMatrix


def Matrix(a=None, *, m: int = 0, n: int = 0, mb: int = 256,
           nb: Optional[int] = None, dtype=jnp.float32,
           grid=None) -> TiledMatrix:
    """General m x n matrix (reference Matrix.hh:26). Every
    constructor here takes ``grid=``: a ProcessGrid over which the
    storage is laid as P('p','q'), each device sent its own block
    straight from the host array `a` (TiledMatrix.from_dense) — how to
    build a matrix larger than one device's memory."""
    if a is not None:
        return TiledMatrix.from_dense(a, mb, nb, grid=grid)
    return TiledMatrix.zeros(m, n, mb, nb, dtype, grid=grid)


def _structured(a, m, n, mb, nb, dtype, mtype, uplo, diag=Diag.NonUnit,
                kl=-1, ku=-1, square=True, grid=None) -> TiledMatrix:
    if a is not None:
        t = TiledMatrix.from_dense(a, mb, nb, mtype=mtype, uplo=uplo,
                                   diag=diag, kl=kl, ku=ku, grid=grid)
    else:
        t = TiledMatrix.zeros(m, n or m, mb, nb, dtype, grid=grid,
                              mtype=mtype, uplo=uplo, diag=diag, kl=kl,
                              ku=ku)
    if square and t.m != t.n:
        raise DimensionError(f"{mtype.name} matrix must be square, "
                             f"got {t.m}x{t.n}")
    return t


def TrapezoidMatrix(uplo: Uplo, a=None, *, m=0, n=0, mb=256, nb=None,
                    diag=Diag.NonUnit, dtype=jnp.float32,
                    grid=None) -> TiledMatrix:
    """Reference TrapezoidMatrix.hh:26."""
    return _structured(a, m, n, mb, nb, dtype, MatrixType.Trapezoid, uplo,
                       diag, square=False, grid=grid)


def TriangularMatrix(uplo: Uplo, a=None, *, n=0, mb=256, nb=None,
                     diag=Diag.NonUnit, dtype=jnp.float32,
                     grid=None) -> TiledMatrix:
    """Reference TriangularMatrix.hh:30."""
    return _structured(a, n, n, mb, nb, dtype, MatrixType.Triangular, uplo,
                       diag, grid=grid)


def SymmetricMatrix(uplo: Uplo, a=None, *, n=0, mb=256, nb=None,
                    dtype=jnp.float32, grid=None) -> TiledMatrix:
    """Reference SymmetricMatrix.hh:26."""
    return _structured(a, n, n, mb, nb, dtype, MatrixType.Symmetric, uplo,
                       grid=grid)


def HermitianMatrix(uplo: Uplo, a=None, *, n=0, mb=256, nb=None,
                    dtype=jnp.float32, grid=None) -> TiledMatrix:
    """Reference HermitianMatrix.hh:26."""
    return _structured(a, n, n, mb, nb, dtype, MatrixType.Hermitian, uplo,
                       grid=grid)


def BandMatrix(kl: int, ku: int, a=None, *, m=0, n=0, mb=256, nb=None,
               dtype=jnp.float32, grid=None) -> TiledMatrix:
    """General band matrix (reference BandMatrix.hh:26). Storage is dense
    tile-aligned with the band mask applied logically — the TPU-native
    trade: HBM is cheap relative to the cost of ragged gather/scatter, and
    band algorithms below restrict computation to the band's tile
    diagonals."""
    return _structured(a, m, n, mb, nb, dtype, MatrixType.GeneralBand,
                       Uplo.General, kl=kl, ku=ku, square=False,
                       grid=grid)


def TriangularBandMatrix(uplo: Uplo, kd: int, a=None, *, n=0, mb=256,
                         nb=None, diag=Diag.NonUnit,
                         dtype=jnp.float32, grid=None) -> TiledMatrix:
    """Reference TriangularBandMatrix.hh:28."""
    kl, ku = (kd, 0) if uplo is Uplo.Lower else (0, kd)
    return _structured(a, n, n, mb, nb, dtype, MatrixType.TriangularBand,
                       uplo, diag, kl=kl, ku=ku, grid=grid)


def HermitianBandMatrix(uplo: Uplo, kd: int, a=None, *, n=0, mb=256,
                        nb=None, dtype=jnp.float32,
                        grid=None) -> TiledMatrix:
    """Reference HermitianBandMatrix.hh:29."""
    kl, ku = (kd, 0) if uplo is Uplo.Lower else (0, kd)
    return _structured(a, n, n, mb, nb, dtype, MatrixType.HermitianBand,
                       uplo, kl=kl, ku=ku, grid=grid)
