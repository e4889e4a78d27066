"""The plain reference of kind `svd`: a singular value decomposition
with both sets of vectors by the textbook route, Householder
bidiagonalisation, the Golub-Kahan implicit-shift QR step on the
bidiagonal matrix with accumulated rotations, and the two
back-transformations, in numpy f32 on the host, with every matrix
product going through one `matmul` argument (lib/plainref.py's style,
whose `matmul_f32` and `matmul_bf16x3` are the two arguments: the
second is the CONTROL, each product computed as the TPU computes an
f32 product at precision `high`). It imports nothing of the program:
not `svd.py`, `polar.py`, `spectral_dc.py` or `jax.lax.linalg.svd`,
nor any polar iteration or divide and conquer.

The algorithm is Golub & Van Loan 5.4.2 (bidiagonalisation) and 8.6.1
and 8.6.2 (the SVD step and its driver); LAPACK's `sgebrd`, `sorgbr`
and `sbdsqr` are the blocked forms of the same. Departures, each noted
where it is made:

* numpy on the host and not `jax.numpy`: control (a) is host
  arithmetic, and the CPU backend ignores a product's `precision`, so
  the lower precision has to be emulated product by product
  (lib/plainref_heev.py's choice, for its reason);
* the rotations are accumulated into the bidiagonal matrix's own
  singular vector matrices Zu and Zv (from the identity) and
  U = Q_L Zu, V = Q_R Zv are one product each at the end (LAPACK's
  `sbdsdc` + `sormbr` shape), where 8.6.2 rotates the accumulated
  reflectors themselves: the same U and V in exact arithmetic, and
  the products are then where a lower precision shows;
* the scalar recurrences of a QR step run in Python floats (f64); the
  vectors they rotate, and everything else, are f32;
* a rotation acts on two ROWS of Z^T (contiguous), not two columns of
  Z;
* a zero on the diagonal inside an unreduced block is chased out by
  row rotations (8.6.2's case) only where it is exactly zero; a
  diagonal entry that is merely tiny takes ordinary steps.
"""

import math

import numpy as np

EPS32 = float(np.finfo(np.float32).eps)


def matmul_f32(a, b):
    return a @ b


def _reflector(x):
    """(v, beta, alpha) with (I - beta v v^T) x = alpha e_1; beta 0
    for a zero x."""
    norm = np.float32(np.linalg.norm(x))
    if norm == 0.0:
        return None, np.float32(0.0), np.float32(0.0)
    alpha = -np.copysign(norm, x[0])
    v = x.copy()
    v[0] -= alpha
    return v, np.float32(2.0) / np.float32(v @ v), alpha


def bidiagonalize(a, mm):
    """Householder reduction of the square `a` (f32, overwritten) to
    upper bidiagonal form. Returns (d, e, left, right): the diagonal,
    the superdiagonal, and the reflectors (v, beta) of each step (a
    left one over rows k:, a right one over columns k+1:)."""
    n = a.shape[0]
    d = np.empty(n, np.float32)
    e = np.zeros(max(n - 1, 0), np.float32)
    left, right = [], []
    for k in range(n):
        v, beta, alpha = _reflector(a[k:, k])
        left.append((v, beta))
        if v is None:
            d[k] = a[k, k]
        else:
            d[k] = alpha
            if k + 1 < n:
                col = v[:, None]
                a[k:, k + 1:] -= mm(beta * col, mm(col.T, a[k:, k + 1:]))
        if k + 1 >= n:
            break
        if k + 2 >= n:
            e[k] = a[k, k + 1]
            right.append((None, np.float32(0.0)))
            continue
        w, gamma, alpha = _reflector(a[k, k + 1:])
        right.append((w, gamma))
        if w is None:
            e[k] = a[k, k + 1]
        else:
            e[k] = alpha
            row = w[None, :]
            a[k + 1:, k + 1:] -= mm(mm(a[k + 1:, k + 1:], row.T),
                                    gamma * row)
    return d, e, left, right


def form_q(n, reflectors, shift, mm):
    """Q = H_0 H_1 ... with H_k = I - beta v v^T acting on indices
    k + shift:, accumulated backwards so each reflector touches only
    the trailing block it can change."""
    q = np.eye(n, dtype=np.float32)
    for k in range(len(reflectors) - 1, -1, -1):
        v, beta = reflectors[k]
        if v is None:
            continue
        col = v[:, None]
        blk = q[k + shift:, k + shift:]
        blk -= mm(beta * col, mm(col.T, blk))
    return q


def _rotate(zt, i, j, c, s):
    """Columns i and j of Z by the plane rotation [[c, -s], [s, c]]
    (rows of Z^T): new i = c i + s j, new j = -s i + c j."""
    c, s = np.float32(c), np.float32(s)
    hi = zt[j].copy()
    zt[j] = c * hi - s * zt[i]
    zt[i] = c * zt[i] + s * hi


def bdsqr(d, e):
    """Singular values and vectors of the upper bidiagonal matrix
    (d, e) by the Golub-Kahan SVD step. Returns (s, zut, zvt) with
    B = Zu diag(s) Zv^T, zut[i] and zvt[i] the left and right vectors
    of s[i] (s >= 0, unsorted)."""
    n = d.shape[0]
    d = [float(x) for x in d]
    e = [float(x) for x in e] + [0.0]
    zut = np.eye(n, dtype=np.float32)
    zvt = np.eye(n, dtype=np.float32)
    m = n - 1
    sweeps = 0
    while m > 0:
        for i in range(m):
            if abs(e[i]) <= EPS32 * (abs(d[i]) + abs(d[i + 1])):
                e[i] = 0.0
        if e[m - 1] == 0.0:
            m -= 1
            continue
        lo = m - 1
        while lo > 0 and e[lo - 1] != 0.0:
            lo -= 1
        sweeps += 1
        if sweeps > 60 * n:
            raise ArithmeticError("bdsqr: no convergence at %d" % m)
        zero = next((i for i in range(lo, m) if d[i] == 0.0), None)
        if zero is not None:
            # 8.6.2: a zero on the diagonal; rotate row `zero` against
            # the rows below it until its superdiagonal entry is gone
            f, e[zero] = e[zero], 0.0
            for j in range(zero + 1, m + 1):
                r = math.hypot(d[j], f)
                c, s = d[j] / r, f / r
                d[j] = r
                _rotate(zut, j, zero, c, s)
                if j < m:
                    f, e[j] = -s * e[j], c * e[j]
            continue
        # the shift: the eigenvalue of the trailing 2 x 2 of B^T B
        # nearer its last entry
        em2 = e[m - 2] if m - 1 > lo else 0.0
        t11 = d[m - 1] ** 2 + em2 ** 2
        t12 = d[m - 1] * e[m - 1]
        t22 = d[m] ** 2 + e[m - 1] ** 2
        delta = (t11 - t22) / 2.0
        mu = t22 if t12 == 0.0 else t22 - t12 * t12 / (
            delta + math.copysign(math.hypot(delta, t12), delta))
        y, z = d[lo] ** 2 - mu, d[lo] * e[lo]
        for k in range(lo, m):
            r = math.hypot(y, z)
            c, s = (1.0, 0.0) if r == 0.0 else (y / r, z / r)
            if k > lo:
                e[k - 1] = r
            f = c * d[k] + s * e[k]
            e[k] = c * e[k] - s * d[k]
            g = s * d[k + 1]
            d[k + 1] = c * d[k + 1]
            _rotate(zvt, k, k + 1, c, s)
            r = math.hypot(f, g)
            c, s = (1.0, 0.0) if r == 0.0 else (f / r, g / r)
            d[k] = r
            f = c * e[k] + s * d[k + 1]
            d[k + 1] = c * d[k + 1] - s * e[k]
            e[k] = f
            _rotate(zut, k, k + 1, c, s)
            if k < m - 1:
                y, z = e[k], s * e[k + 1]
                e[k + 1] = c * e[k + 1]
    s = np.array(d, np.float32)
    neg = s < 0
    zvt[neg] *= np.float32(-1.0)
    return np.abs(s), zut, zvt


def svd(a, matmul=matmul_f32):
    """(U, s descending, Vh) of the real square matrix `a`, f32 in and
    out: a = U diag(s) Vh."""
    a = np.array(a, np.float32, order="C")
    n = a.shape[0]
    d, e, left, right = bidiagonalize(a, matmul)
    s, zut, zvt = bdsqr(d, e)
    order = np.argsort(-s, kind="stable")
    u = matmul(form_q(n, left, 0, matmul),
               np.ascontiguousarray(zut[order].T))
    v = matmul(form_q(n, right, 1, matmul),
               np.ascontiguousarray(zvt[order].T))
    return u, s[order], np.ascontiguousarray(v.T)


SOLVERS = {"svd": svd}
