"""Inputs of kind `heev` from the seed (lib/gen.py makes the other
kinds' and could not be edited by the PR that added this one): one
dense real symmetric matrix with a stated spectrum, made on the host
in numpy in O(n^2 log n).

    A = U diag(lam) U^T,   U = D2 H D1 H,   H = Hadamard / sqrt(n)

|lam| runs geometrically from 1 down to 1/cond (SLATE's and LAPACK's
matgen kind `heev` with the `geo` distribution) and each eigenvalue
takes its sign at random, independently of the others, as matgen's
symmetric kinds draw it (`spectrum`). The signs are ONE draw, named by
the configuration (`matrix.sign_seed`), so the multiset of eigenvalues
is the configuration's and `--seed` decides everything else: the
order of the eigenvalues along U's columns and the two diagonals of
random signs D1 and D2 that make U a dense orthogonal matrix whose
every entry has magnitude about 1/sqrt(n), applied by two fast
Walsh-Hadamard transforms instead of a host QR of a Gaussian matrix
(n^3: minutes at n=8192). A seed never changes the amount of work
(lib/gen.py's rule): a divide and conquer solver's tree follows the
spectrum, and with the signs drawn anew from every seed the wall of
one solve read 3.83 and 4.73 s on two seeds (PR 33). The order is
D2 H D1 H, not H D1 H D2: a diagonal of signs next to diag(lam)
cancels against itself.

Half the eigenvalues lie within 1e-2 of zero. The mean of the spectrum
is a draw of some 0.003 to 0.005 either way (-0.00523 at n=8192 under
sign_seed 10, with 4,144 eigenvalues negative), so a solver that
splits at the median of the diagonal, which concentrates at that mean,
splits INSIDE the cluster and off its centre (2,368 of 8,192 under the
split point, 3,941 singular values of H - sigma I under 1e-2): the
input the polar iteration's lower-bound schedule has to survive, and
a lopsided tree. ||A||_2 = 1 and cond_2(A) = cond up to the f32
rounding of the entries (some 4e-8 in norm at n=8192, a thousandth of
the smallest |lam| at cond 1e4). n is a power of two.
"""

import numpy as np

from benchmarks.lib import gen


def fwht0(x, cols=512):
    """Unnormalised fast Walsh-Hadamard transform along axis 0 of the
    2-D array x, in place, a block of columns at a time (a block's
    butterflies stay in cache)."""
    n = x.shape[0]
    for c in range(0, x.shape[1], cols):
        blk = x[:, c:c + cols]
        w = blk.shape[1]
        h = 1
        while h < n:
            y = blk.reshape(n // (2 * h), 2, h, w)
            lo = y[:, 0] + y[:, 1]
            y[:, 1] = y[:, 0] - y[:, 1]
            y[:, 0] = lo
            h *= 2
    return x


def spectrum(r, n, cond, sign_seed):
    """lam (f64): magnitudes geometric from 1 to 1/cond, each with an
    independent random sign (matgen), in a seeded order. The signs
    are the configuration's one draw (`sign_seed`), so every seed has
    the same multiset of eigenvalues; `r`, the seed's generator, only
    places them along U's columns."""
    mag = float(cond) ** (-np.arange(n) / max(n - 1, 1))
    signs = gen.rng(sign_seed, "signs").choice([-1.0, 1.0], size=n)
    return (mag * signs)[r.permutation(n)]


def geo_symmetric(r, n, cond, sign_seed):
    """(A in f32, lam in f64 ascending) from the generator `r`
    (lib/gen.py rng). A is exactly symmetric."""
    if n & (n - 1):
        raise ValueError("heevgen: n = %d is not a power of two" % n)
    lam = spectrum(r, n, cond, sign_seed)
    d1 = r.choice([-1.0, 1.0], size=n)
    d2 = r.choice([-1.0, 1.0], size=n)
    # T = H diag(lam) H is dyadic-circulant: T[i, j] = t[i xor j] with
    # t the transform of lam, so the first two transforms are one of
    # length n and a gather
    t = fwht0(lam[:, None].copy())[:, 0] / n
    idx = np.arange(n)
    a = t[idx[:, None] ^ idx[None, :]]
    a *= d1[:, None]
    a *= d1[None, :]
    # H S H for symmetric S: transform the columns, transpose,
    # transform again
    a = np.ascontiguousarray(fwht0(a).T)
    fwht0(a)
    a *= d2[:, None] / n
    a *= d2[None, :]
    a = a.astype(np.float32)
    a = (a + a.T) * np.float32(0.5)
    return a, np.sort(lam)
