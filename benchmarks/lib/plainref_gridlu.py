"""The plain reference of the general solve on a grid (kinds/gridlu.py):
right-looking blocked LU with partial pivoting of a dense f32 matrix in
numpy on the host, the packed factor and the pivots handed back as
LAPACK's `getrf` hands them, then the two triangular solves. It
imports nothing of the program.

The loop is ScaLAPACK's `pdgetrf` and SLATE's `src/getrf.cc` without
their grid: for each block column the panel is factored over every row
not yet eliminated (lib/plainref.py's `_lu_rec`, whole rows swapped,
so the columns left of the panel take the exchange as they do there),
one unit-lower solve gives the block row of U, and ONE product updates
the trailing matrix. Every matrix product goes through one `matmul`
argument: `plainref.matmul_f32` is the reference,
`plainref.matmul_bf16x3` the control one precision below the `highest`
products the configuration states; `pivot=False` is the third control:
the same loop with the row exchanges switched off (LU without
pivoting on the same data; lib/plainref_streamlu.py's base case).

The trailing product is taken a tile of the result at a time
(`TILE`), so that the control's bfloat16 pieces of a 49152-order
operand are a few hundred MB and not four more matrices; a tile's
sums are the sums the whole product has. `inplace=True` factors the
caller's array where it lies (the controls at the cell's size hold
ONE 9.66 GB matrix and make A again from the seed for the residual).

Departures from the reference library: one process and no tiles, so
no lookahead, no panel-column rank set and no broadcast; f32 for the
tester's `d`.
"""

import numpy as np
from scipy.linalg import solve_triangular

from . import plainref
from .plainref_streamlu import _lu_rec_nopiv, getrs, growth  # noqa: F401

#: block columns of the right-looking loop (the cell's tile, and any
#: other: the pivots do not depend on it)
NB = 512
#: order of a tile of the trailing product
TILE = 8192


def _update(c, a, b, mm):
    """c -= a @ b through `mm`, a TILE x TILE piece of c at a time."""
    for i in range(0, c.shape[0], TILE):
        for j in range(0, c.shape[1], TILE):
            c[i:i + TILE, j:j + TILE] -= mm(a[i:i + TILE], b[:, j:j + TILE])


def getrf(a, matmul=plainref.matmul_f32, pivot=True, nb=NB, inplace=False):
    """(LU packed, ipiv): unit-lower L below the diagonal, U on and
    above, and the sequential swap targets (row j exchanged with row
    ipiv[j] >= j, in order)."""
    lu = a if inplace else np.array(a, np.float32, order="C")
    n = lu.shape[0]
    piv = np.arange(n)
    with np.errstate(all="ignore"):     # the unpivoted control may overflow
        for k0 in range(0, n, nb):
            k1 = min(k0 + nb, n)
            if pivot:
                plainref._lu_rec(lu, k0, k1, piv, matmul)
            else:
                _lu_rec_nopiv(lu, k0, k1, matmul)
            if k1 < n:
                lu[k0:k1, k1:] = solve_triangular(
                    lu[k0:k1, k0:k1], lu[k0:k1, k1:], lower=True,
                    unit_diagonal=True, check_finite=False)
                _update(lu[k1:, k1:], lu[k1:, k0:k1], lu[k0:k1, k1:],
                        matmul)
    return lu, piv


def gesv(a, b, matmul=plainref.matmul_f32, pivot=True, nb=NB,
         inplace=False):
    """((LU, ipiv), X), the contract of `st.gesv`'s (F, X)."""
    lu, ipiv = getrf(a, matmul, pivot, nb, inplace)
    return (lu, ipiv), getrs(lu, ipiv, b)
