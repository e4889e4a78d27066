"""The plain reference of kind `lstsq`: least squares by Householder
QR in numpy f32 on the host, with every matrix product going through
one `matmul` argument (lib/plainref.py's style, whose `matmul_f32` and
`matmul_bf16x3` are the two arguments: the second is the CONTROL, each
product computed as the TPU computes an f32 product at precision
`high`). It imports nothing of the program.

The algorithm is the textbook one (Golub & Van Loan 5.2, LAPACK
geqrf + ormqr + trtrs): reflect each column onto e_1, accumulate the
reflectors of a block in compact WY form Q = I - V T V^T, update the
columns to the right by C -= V (T^T (V^T C)). Departures, each noted
where it is made:

* recursive blocking (Elmroth and Gustavson 2000) instead of LAPACK's
  fixed panel width, as lib/plainref.py's LU and Cholesky recurse:
  the same reflectors, merged T12 = -T1 (V1^T V2) T2;
* B rides along as extra columns of the matrix, so Q^T B is formed by
  the same updates and no Q is applied afterwards;
* column norms and the final triangular solve stay in plain f32 (the
  control lowers the matrix products only, which is where a later
  change to the program would be tempted to lower them).
"""

import numpy as np
from scipy.linalg import solve_triangular

QR_BASE = 8


def _reflect(a, j, cend, mm):
    """Householder reflector of a[j:, j] in place (beta on the
    diagonal, v below it, v[0] = 1 implied), applied to columns
    j+1:cend. Returns tau."""
    x = a[j:, j]
    alpha = x[0]
    norm = np.float32(np.linalg.norm(x))
    if norm == 0.0:
        return np.float32(0.0)
    beta = -np.copysign(norm, alpha)
    tau = (beta - alpha) / beta
    x[1:] /= (alpha - beta)
    x[0] = beta
    if j + 1 < cend:
        v = np.concatenate(([np.float32(1.0)], x[1:]))[:, None]
        w = mm(v.T, a[j:, j + 1:cend])
        a[j:, j + 1:cend] -= mm(v * tau, w)
    return tau


def _unit_lower(a, c0, c1):
    """V of columns c0:c1: rows c0:, ones on the diagonal, zeros
    above it."""
    v = np.array(a[c0:, c0:c1])
    w = c1 - c0
    v[:w][np.triu_indices(w)] = 0.0
    v[np.arange(w), np.arange(w)] = 1.0
    return v


def _qr_rec(a, c0, c1, cend, mm):
    """Factor columns c0:c1 (rows c0:) in place and apply Q^T to
    columns c1:cend. Returns the (w, w) upper triangular T."""
    w = c1 - c0
    if w <= QR_BASE:
        t = np.zeros((w, w), np.float32)
        for j in range(c0, c1):
            # the reflectors of the base block reach only its own
            # columns; the block's compact WY form updates the rest
            tau = _reflect(a, j, c1, mm)
            k = j - c0
            t[k, k] = tau
            if k:
                vprev = _unit_lower(a, c0, j)[k:]
                vj = np.concatenate(([np.float32(1.0)],
                                     a[j + 1:, j]))[:, None]
                t[:k, k:k + 1] = -tau * mm(t[:k, :k], mm(vprev.T, vj))
        _apply(a, c0, c1, c1, cend, t, mm)
        return t
    cm = c0 + w // 2
    t1 = _qr_rec(a, c0, cm, c1, mm)
    t2 = _qr_rec(a, cm, c1, c1, mm)
    v1 = _unit_lower(a, c0, cm)[cm - c0:]
    v2 = _unit_lower(a, cm, c1)
    t = np.zeros((w, w), np.float32)
    h = cm - c0
    t[:h, :h], t[h:, h:] = t1, t2
    t[:h, h:] = -mm(t1, mm(mm(v1.T, v2), t2))
    _apply(a, c0, c1, c1, cend, t, mm)
    return t


def _apply(a, c0, c1, lo, hi, t, mm):
    """a[c0:, lo:hi] -= V (T^T (V^T a[c0:, lo:hi])), V of columns
    c0:c1."""
    if lo >= hi:
        return
    v = _unit_lower(a, c0, c1)
    a[c0:, lo:hi] -= mm(v, mm(t.T, mm(v.T, a[c0:, lo:hi])))


def matmul_f32(a, b):
    return a @ b


def lstsq_qr(a, b, matmul=matmul_f32):
    """X minimizing ||A X - B||_2 column by column (f32 in, f32 out),
    A (m, n) of full column rank, m >= n."""
    m, n = a.shape
    ab = np.empty((m, n + b.shape[1]), np.float32)
    ab[:, :n], ab[:, n:] = a, b
    _qr_rec(ab, 0, n, ab.shape[1], matmul)
    return solve_triangular(ab[:n, :n], ab[:n, n:], lower=False,
                            check_finite=False)


SOLVERS = {"gels": lstsq_qr}
