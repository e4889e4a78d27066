"""The plain reference of the mixed-precision solve (reference
src/gesv_mixed.cc): LU in bfloat16, iterative refinement in f32, in
numpy on the host. It imports nothing of the program; the recursive
LU is lib/plainref.py's, given a product of its own.

The factor: A rounded to bfloat16; partial pivoting; every matrix
product bfloat16 x bfloat16 accumulated in f32 (both operands rounded
to bfloat16 and multiplied in f32: products of bfloat16 values are
exact in f32, so this is the same arithmetic on any machine); the
finished factor STORED rounded to bfloat16. The refinement: x0 from
the lo solve of b; then r = b - A x with the f32 A through `resid`'s
product (f32 for the reference; `plainref.matmul_bf16x3` or
`matmul_bf16` for control (a)), a correction by the lo solve, SLATE's
stopping rule max|r| <= max|x| ||A||_inf eps sqrt(n), at most
`itermax` sweeps, and one polish step once it is met.

Departures from gesv_mixed.cc, each the program's too: the pair is
f32 / bfloat16 where SLATE's is f64 / f32 (the chip has neither f64
nor f16 matrix units); the trailing matrix between the recursion's
steps is kept in f32 and only the finished factor is rounded (SLATE
holds everything in the lo type; the program rounds the trailing
matrix at each block step); the lo solve rounds its right-hand side
and its answer to bfloat16 and substitutes in f32; the polish step is
this library's (one more lo solve after the criterion is met, not
counted); no fallback here: the caller sees `converged`.
"""

import numpy as np
from scipy.linalg import solve_triangular

from . import plainref

BF16 = plainref.BF16


def round_bf16(x):
    """`x` rounded to bfloat16, held in f32."""
    return np.asarray(x, np.float32).astype(BF16).astype(np.float32)


def matmul_bf16(a, b):
    """ONE bfloat16 pass accumulated in f32: the lo product."""
    return round_bf16(np.ascontiguousarray(a)) @ \
        round_bf16(np.ascontiguousarray(b))


def lu_bf16(a):
    """(factor rounded to bfloat16, held in f32; pivots) of A rounded
    to bfloat16."""
    lu = np.array(round_bf16(a), order="C")
    piv = np.arange(lu.shape[0])
    plainref._lu_rec(lu, 0, lu.shape[0], piv, matmul_bf16)
    return round_bf16(lu), piv


def lo_solve(lu, piv, rhs):
    """The lo solve of an f32 right-hand side: rounded, swapped,
    substituted against the stored factor, rounded."""
    y = round_bf16(rhs)
    for j, p in enumerate(piv):
        if p != j:
            y[[j, p]] = y[[p, j]]
    y = solve_triangular(lu, y, lower=True, unit_diagonal=True,
                         check_finite=False)
    return round_bf16(solve_triangular(lu, y, lower=False,
                                       check_finite=False))


def refine(a, b, lu, piv, resid=plainref.matmul_f32, itermax=30):
    """(x, sweeps, converged) for f32 A, b and the lo factor."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    n = a.shape[0]
    cte = np.float32(np.abs(a).sum(axis=1).max()
                     * np.finfo(np.float32).eps * np.sqrt(np.float32(n)))

    def met(x, r):
        return bool(np.abs(r).max() <= np.abs(x).max() * cte)

    x = lo_solve(lu, piv, b)
    r = b - resid(a, x)
    it = 0
    while not met(x, r) and it < itermax:
        x = x + lo_solve(lu, piv, r)
        r = b - resid(a, x)
        it += 1
    ok = met(x, r)
    if ok and itermax > 0:
        x = x + lo_solve(lu, piv, r)
    return x, it, ok


def gesv_mixed(a, b, resid=plainref.matmul_f32, itermax=30):
    """X with A X = B by bfloat16 LU and f32 refinement:
    (x f32, sweeps, converged)."""
    lu, piv = lu_bf16(a)
    return refine(a, b, lu, piv, resid, itermax)
