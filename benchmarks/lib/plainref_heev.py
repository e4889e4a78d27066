"""The plain reference of kind `heev`: a real symmetric
eigendecomposition with all vectors by the textbook route, Householder
tridiagonalisation, implicit-shift QL on the tridiagonal matrix with
accumulated rotations, and the back-transformation, in numpy f32 on
the host, with every matrix product going through one `matmul`
argument (lib/plainref.py's style, whose `matmul_f32` and
`matmul_bf16x3` are the two arguments: the second is the CONTROL, each
product computed as the TPU computes an f32 product at precision
`high`). It imports nothing of the program: not `spectral_dc.py`,
`polar.py` or `eig.py`, nor any polar iteration or divide and conquer.

The algorithm is Golub & Van Loan 8.3.1 (tridiagonalisation) and
EISPACK's `tql2` (Numerical Recipes `tqli`); LAPACK's `ssytrd`,
`sorgtr` and `ssteqr` are the blocked forms of the same. Departures,
each noted where it is made:

* numpy on the host and not `jax.numpy`: control (a) is host
  arithmetic, and the CPU backend ignores a product's `precision`, so
  the lower precision has to be emulated product by product;
* the QL rotations are accumulated into the tridiagonal matrix's own
  eigenvector matrix Z (from the identity) and V = Q Z is one product
  at the end (LAPACK's `stedc` + `ormtr` shape), where `tql2` rotates
  Q itself: the same V in exact arithmetic, and the product is then
  where a lower precision shows;
* the scalar recurrences of a QL sweep run in Python floats (f64);
  the vectors they rotate, and everything else, are f32;
* a rotation acts on two ROWS of Z^T (contiguous), not two columns
  of Z.
"""

import math

import numpy as np

EPS32 = float(np.finfo(np.float32).eps)


def matmul_f32(a, b):
    return a @ b


def tridiagonalize(a, mm):
    """Householder reduction of symmetric `a` (f32, overwritten) to
    tridiagonal form. Returns (d, e, vs, betas): the diagonal, the
    subdiagonal, and the reflectors I - beta v v^T of each step
    (v over rows k+1:)."""
    n = a.shape[0]
    d = np.empty(n, np.float32)
    e = np.zeros(max(n - 1, 0), np.float32)
    vs, betas = [], []
    for k in range(n - 2):
        x = a[k + 1:, k]
        norm = np.float32(np.linalg.norm(x))
        d[k] = a[k, k]
        if norm == 0.0:
            vs.append(None)
            betas.append(np.float32(0.0))
            continue
        alpha = -np.copysign(norm, x[0])
        v = x.copy()
        v[0] -= alpha
        beta = np.float32(2.0) / np.float32(v @ v)
        col = v[:, None]
        p = beta * mm(a[k + 1:, k + 1:], col)
        w = p - (beta * np.float32(0.5) * np.float32(p[:, 0] @ v)) * col
        a[k + 1:, k + 1:] -= mm(col, w.T) + mm(w, col.T)
        e[k] = alpha
        vs.append(v)
        betas.append(beta)
    if n >= 2:
        d[n - 2], e[n - 2] = a[n - 2, n - 2], a[n - 1, n - 2]
    d[n - 1] = a[n - 1, n - 1]
    return d, e, vs, betas


def form_q(n, vs, betas, mm):
    """Q = H_0 H_1 ... H_{n-3}, accumulated backwards so each
    reflector touches only the trailing block it can change."""
    q = np.eye(n, dtype=np.float32)
    for k in range(len(vs) - 1, -1, -1):
        if vs[k] is None:
            continue
        col = vs[k][:, None]
        blk = q[k + 1:, k + 1:]
        blk -= mm(betas[k] * col, mm(col.T, blk))
    return q


def tql2(d, e):
    """Eigenvalues and eigenvectors of the symmetric tridiagonal
    matrix (d, e) by the implicit-shift QL iteration. Returns (w, zt)
    with zt[i] the eigenvector of w[i] (unsorted)."""
    n = d.shape[0]
    d = [float(x) for x in d]
    e = [float(x) for x in e] + [0.0]
    zt = np.eye(n, dtype=np.float32)
    for l in range(n):
        for sweep in range(61):
            m = l
            while m < n - 1 and \
                    abs(e[m]) > EPS32 * (abs(d[m]) + abs(d[m + 1])):
                m += 1
            if m == l:
                break
            if sweep == 60:
                raise ArithmeticError("tql2: no convergence at %d" % l)
            g = (d[l + 1] - d[l]) / (2.0 * e[l])
            r = math.hypot(g, 1.0)
            g = d[m] - d[l] + e[l] / (g + math.copysign(r, g))
            s = c = 1.0
            p = 0.0
            for i in range(m - 1, l - 1, -1):
                f, b = s * e[i], c * e[i]
                r = math.hypot(f, g)
                e[i + 1] = r
                if r == 0.0:
                    d[i + 1] -= p
                    e[m] = 0.0
                    break
                s, c = f / r, g / r
                g = d[i + 1] - p
                r = (d[i] - g) * s + 2.0 * c * b
                p = s * r
                d[i + 1] = g + p
                g = c * r - b
                hi = zt[i + 1].copy()
                zt[i + 1] = np.float32(s) * zt[i] + np.float32(c) * hi
                zt[i] = np.float32(c) * zt[i] - np.float32(s) * hi
            else:
                d[l] -= p
                e[l] = g
                e[m] = 0.0
    return np.array(d, np.float32), zt


def eigh(a, matmul=matmul_f32):
    """(w ascending, V with V[:, i] the eigenvector of w[i]) of the
    real symmetric matrix `a`, f32 in and out."""
    a = np.array(a, np.float32, order="C")
    n = a.shape[0]
    d, e, vs, betas = tridiagonalize(a, matmul)
    w, zt = tql2(d, e)
    order = np.argsort(w, kind="stable")
    v = matmul(form_q(n, vs, betas, matmul),
               np.ascontiguousarray(zt[order].T))
    return w[order], v


SOLVERS = {"heev": eigh}
