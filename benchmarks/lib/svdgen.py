"""Inputs of kind `svd` from the seed (lib/gen.py makes the other
kinds' and could not be edited by the PR that added this one): one
dense real square matrix with stated singular values, made on the
host in numpy in O(n^2 log n).

    A = U diag(s) V^T,   U = D2 H D1 H,   V = D4 H D3 H,
    H = Hadamard / sqrt(n)

s runs geometrically from 1 down to 1/cond (SLATE's and LAPACK's
matgen kind `svd` with the `geo` distribution): the multiset of
singular values is the configuration's, whatever the seed, so a seed
never changes the amount of work (lib/gen.py's rule; a divide and
conquer solver's tree follows the spectrum). `--seed` places the
singular values in a seeded order along the columns of U and V and
draws the four diagonals of random signs D1..D4 that make U and V
dense orthogonal matrices whose every entry has magnitude about
1/sqrt(n), each applied by two fast Walsh-Hadamard transforms
(lib/heevgen.py's, whose order D2 H D1 H it keeps: a diagonal of
signs next to diag(s) would cancel against nothing here, but U and V
are then the same family as `heev-geo-n8192-cond1e4`'s U).

||A||_2 = 1 and cond_2(A) = cond up to the f32 rounding of the
entries (some 4e-8 in norm at n=8192). n is a power of two.
"""

import numpy as np

from benchmarks.lib.heevgen import fwht0


def spectrum(r, n, cond):
    """s (f64): geometric from 1 to 1/cond, in the seed's order along
    the columns of U and V. Every seed has the same multiset."""
    mag = float(cond) ** (-np.arange(n) / max(n - 1, 1))
    return mag[r.permutation(n)]


def geo_general(r, n, cond):
    """(A in f32, s in f64 descending) from the generator `r`
    (lib/gen.py rng)."""
    if n & (n - 1):
        raise ValueError("svdgen: n = %d is not a power of two" % n)
    s = spectrum(r, n, cond)
    d1u, d2u, d1v, d2v = (r.choice([-1.0, 1.0], size=n) for _ in range(4))
    # T = H diag(s) H is dyadic-circulant: T[i, j] = t[i xor j] with t
    # the transform of s, so the two inner transforms are one of
    # length n and a gather
    t = fwht0(s[:, None].copy())[:, 0] / n
    idx = np.arange(n)
    m = t[idx[:, None] ^ idx[None, :]]
    m *= d1u[:, None]
    m *= d1v[None, :]
    # H M H for a general M: transform the columns, transpose,
    # transform again; that is (H M H)^T, so the outer signs go on
    # swapped and the last transpose undoes it
    m = np.ascontiguousarray(fwht0(m).T)
    fwht0(m)
    m *= d2v[:, None] / n
    m *= d2u[None, :]
    return np.ascontiguousarray(m.T.astype(np.float32)), np.sort(s)[::-1]


def mean_split_sizes(lam, floor):
    """[(rows, rows under the split point)] of every block of over
    `floor` rows in the tree of a solver that splits a block at the
    mean of its eigenvalues `lam` (the median of a block's diagonal
    concentrates there; the traced sizes follow this to a few rows):
    the arithmetic the configuration's law was checked with."""
    out, todo = [], [np.sort(np.asarray(lam, np.float64))]
    while todo:
        blk = todo.pop()
        if blk.size <= floor:
            continue
        k = int((blk < blk.mean()).sum())
        out.append((int(blk.size), k))
        todo += [blk[:k], blk[k:]]
    return sorted(out, reverse=True)
