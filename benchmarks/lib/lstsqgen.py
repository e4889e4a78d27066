"""Inputs of kind `lstsq` from the seed (lib/gen.py makes the other
kinds' and could not be edited by the PR that added this one): a tall
full-rank least-squares problem whose conditioning is stated, made on
the host in numpy.

    A = (G / sqrt(m)) W,   W = V diag(s) V^T,   B = A x0 + noise * r

G is an (m, n) standard-normal matrix, so G / sqrt(m) has singular
values in [1 - sqrt(n/m), 1 + sqrt(n/m)] (Marchenko-Pastur edges:
0.75 to 1.25 at 65536 x 4096, cond 1.67); V is a seeded n x n
orthogonal matrix (the Q of a Gaussian matrix's QR) and s runs
geometrically from 1 down to 1/cond, SLATE's and LAPACK's matgen
`svd` kind with the `geo` distribution. The ill-conditioning is in
the singular VECTORS: a column scaling would leave the Cholesky
factor of the Gram matrix as accurate as before and test nothing.
Since sigma_i(G/sqrt(m)) bounds the change either way,

    cond / 1.67 <= cond_2(A) <= 1.67 cond        (m = 16 n),

up to the f32 rounding of the product (a relative 1e-7 of ||A||,
a thousandth of the smallest singular value at cond 1e4).

B is a noisy fit, not a consistent system: x0 and r are standard
normal and each column of r is scaled to `noise` times the root mean
square of the same column of A x0. At noise 1e-3 (60 dB) the
residual's share of the solution's sensitivity, cond^2 ||r|| /
(||A|| ||x||), is about 1.8 times the cond of a consistent system at
cond 1e4: neither zero nor dominant.
"""

import numpy as np


def orthogonal(r, n):
    """A seeded n x n orthogonal matrix (f64)."""
    q, _ = np.linalg.qr(r.standard_normal((n, n)))
    return q


def tall_lstsq(r, m, n, nrhs, cond, noise):
    """(A, B) in f32 from the generator `r` (lib/gen.py rng)."""
    g = r.standard_normal((m, n), dtype=np.float32)
    g *= np.float32(1.0 / np.sqrt(m))
    a = g if cond is None else g @ spectrum(r, n, cond)
    x0 = r.standard_normal((n, nrhs), dtype=np.float32)
    b = a @ x0
    rms = np.sqrt(np.mean(b * b, axis=0, dtype=np.float64))
    rr = r.standard_normal((m, nrhs), dtype=np.float32)
    rr *= (noise * rms).astype(np.float32)
    b += rr
    return a, b


def spectrum(r, n, cond):
    """W = V diag(s) V^T in f32, s geometric from 1 to 1/cond."""
    v = orthogonal(r, n)
    s = float(cond) ** (-np.arange(n) / max(n - 1, 1))
    return ((v * s) @ v.T).astype(np.float32)
