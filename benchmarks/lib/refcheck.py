"""The comparisons that decide `correct`: f64 residuals on the host,
outside every timed region (copied from chip_smoke.py, which passed on
the chip in PR 22)."""

import numpy as np

EPS32 = float(np.finfo(np.float32).eps)


def hpl_resid(a64, x, b64, n):
    """HPL's scaled residual ||Ax-b||_inf / (eps n (||A||_inf
    ||x||_inf + ||b||_inf)), eps of f32, in f64."""
    x64 = np.asarray(x, np.float64)
    r = np.abs(a64 @ x64 - b64).sum(axis=1).max()
    an = np.abs(a64).sum(axis=1).max()
    xn = np.abs(x64).sum(axis=1).max()
    bn = np.abs(b64).sum(axis=1).max()
    return float(r / (EPS32 * n * (an * xn + bn)))


def hpl_resid_blocked(a32, x, b, n, rows=2048):
    """The same number for a large f32 matrix on the host, its rows
    cast to f64 a block at a time (no second copy of A)."""
    x64 = np.asarray(x, np.float64)
    b64 = np.asarray(b, np.float64)
    r = an = 0.0
    for i in range(0, n, rows):
        blk = a32[i:i + rows].astype(np.float64)
        r = max(r, float(np.abs(blk @ x64 - b64[i:i + rows])
                         .sum(axis=1).max()))
        an = max(an, float(np.abs(blk).sum(axis=1).max()))
    xn = np.abs(x64).sum(axis=1).max()
    bn = np.abs(b64).sum(axis=1).max()
    return float(r / (EPS32 * n * (an * xn + bn)))


def factor_sample(n, r, count=128):
    """Rows at which a Cholesky factor is checked: `count` drawn from
    the seed, the last row (the longest accumulation) among them."""
    rows = set(int(i) for i in r.choice(n, size=min(count, n) - 1,
                                        replace=False)) | {n - 1}
    return np.array(sorted(rows))


def factor_resid(a_ss, l_rows, rows):
    """The rms of A - L L^T over the sampled rows x sampled rows, in
    f64, over eps_f32 max(diag A): the factorization's own residual,
    which no rounding of X hides (the rms, not the max: it is steady
    from seed to seed). `l_rows` are the sampled rows of L, whatever
    lies above the diagonal ignored."""
    l64 = np.asarray(l_rows, np.float64)
    l64[np.arange(l64.shape[1])[None, :] > np.asarray(rows)[:, None]] = 0.0
    a64 = np.asarray(a_ss, np.float64)
    r = a64 - l64 @ l64.T
    scale = EPS32 * np.abs(np.diag(a64)).max()
    return float(np.sqrt(np.mean(r * r)) / scale)
