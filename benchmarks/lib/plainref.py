"""The plain reference: dense LU and Cholesky solves in numpy on the
host, recursive, with every matrix product going through one
`matmul` argument. It imports nothing of the program.

With `matmul_f32` it is a straightforward f32 solve. With
`matmul_bf16x3` it is the CONTROL: the same solve with each product
computed as the TPU computes an f32 product at precision `high` —
both operands split into two bfloat16 pieces, three of the four piece
products kept (hi*hi + hi*lo + lo*hi), accumulated in f32 — the
nearest precision below the `highest` (six-pass) products the
configurations state. Products of bfloat16 values are exact in f32, so
the emulation is the same arithmetic on any machine. Triangular
solves and the small base blocks stay in f32: the control is lower
precision in its matrix products only, which is where a later change
would be tempted to lower it.
"""

import ml_dtypes
import numpy as np
from scipy.linalg import solve_triangular

BF16 = ml_dtypes.bfloat16
LU_BASE = 8
CHOL_BASE = 16


def matmul_f32(a, b):
    return a @ b


def _split(x):
    hi = x.astype(BF16).astype(np.float32)
    return hi, (x - hi).astype(BF16).astype(np.float32)


def matmul_bf16x3(a, b):
    ah, al = _split(np.ascontiguousarray(a))
    bh, bl = _split(np.ascontiguousarray(b))
    return ah @ bh + (ah @ bl + al @ bh)


def _lu_rec(a, c0, c1, piv, mm):
    """Factor columns c0:c1 (rows c0:) in place, whole rows swapped."""
    if c1 - c0 <= LU_BASE:
        for j in range(c0, c1):
            p = j + int(np.argmax(np.abs(a[j:, j])))
            piv[j] = p
            if p != j:
                a[[j, p], :] = a[[p, j], :]
            a[j + 1:, j] /= a[j, j]
            if j + 1 < c1:
                a[j + 1:, j + 1:c1] -= mm(a[j + 1:, j:j + 1],
                                          a[j:j + 1, j + 1:c1])
        return
    cm = c0 + (c1 - c0) // 2
    _lu_rec(a, c0, cm, piv, mm)
    a[c0:cm, cm:c1] = solve_triangular(a[c0:cm, c0:cm], a[c0:cm, cm:c1],
                                       lower=True, unit_diagonal=True,
                                       check_finite=False)
    a[cm:, cm:c1] -= mm(a[cm:, c0:cm], a[c0:cm, cm:c1])
    _lu_rec(a, cm, c1, piv, mm)


def lu_solve(a, b, matmul=matmul_f32):
    """X with A X = B by LU with partial pivoting (f32 in, f32 out)."""
    lu = np.array(a, np.float32, order="C")
    n = lu.shape[0]
    piv = np.arange(n)
    _lu_rec(lu, 0, n, piv, matmul)
    x = np.array(b, np.float32)
    for j, p in enumerate(piv):
        if p != j:
            x[[j, p]] = x[[p, j]]
    y = solve_triangular(lu, x, lower=True, unit_diagonal=True,
                         check_finite=False)
    return solve_triangular(lu, y, lower=False, check_finite=False)


def _chol_rec(a, c0, c1, mm):
    """Lower Cholesky of the trailing block a[c0:, c0:] restricted to
    columns c0:c1, in place (the block below/right is updated)."""
    if c1 - c0 <= CHOL_BASE:
        a[c0:c1, c0:c1] = np.linalg.cholesky(a[c0:c1, c0:c1])
        return
    cm = c0 + (c1 - c0) // 2
    _chol_rec(a, c0, cm, mm)
    l21 = solve_triangular(a[c0:cm, c0:cm], a[cm:c1, c0:cm].T, lower=True,
                           check_finite=False).T
    a[cm:c1, c0:cm] = l21
    a[cm:c1, cm:c1] -= mm(l21, l21.T)
    _chol_rec(a, cm, c1, mm)


def chol_solve(a, b, matmul=matmul_f32, factor=None):
    """X with A X = B for SPD A by Cholesky (reads the lower triangle).
    `factor`, a list, receives L."""
    l = np.array(a, np.float32, order="C")
    n = l.shape[0]
    _chol_rec(l, 0, n, matmul)
    l = np.tril(l)
    if factor is not None:
        factor.append(l)
    y = solve_triangular(l, np.asarray(b, np.float32), lower=True,
                         check_finite=False)
    return solve_triangular(l, y, lower=True, trans="T",
                            check_finite=False)


SOLVERS = {"gesv": lu_solve, "posv": chol_solve, "posv_ooc": chol_solve}
