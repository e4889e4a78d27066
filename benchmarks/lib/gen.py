"""Inputs from the seed. Everything a cell solves is made here, in
numpy, from `--seed`; nothing is read from a file.

A seed never changes the AMOUNT of work: the served sizes and the
arrival gaps are fixed quantile sets of their laws, and the seed only
shuffles their order and draws the entries (so two seeds differ like
two runs of one seed, not like two workloads).
"""

import math

import numpy as np


def rng(seed, stream):
    """One generator per named stream of one seed (any whole number;
    the driver's exceed 2**31)."""
    tag = int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.default_rng([int(seed), tag])


def general(r, n):
    """Dense standard-normal f32 system matrix."""
    return r.standard_normal((n, n), dtype=np.float32)


def rhs(r, n, nrhs):
    return r.standard_normal((n, nrhs), dtype=np.float32)


def spd_gram(r, n, m=64, delta=1.0):
    """Dense SPD that is NOT diagonally dominant: G G^T / m + delta I
    with G an (n, m) standard-normal matrix — a regularised low-rank
    covariance (an ensemble or factor-model covariance), made in
    O(n^2 m). Its off-diagonal mass is comparable to its diagonal, so
    its Cholesky factor is made of the matrix products; chip_smoke.py's
    diagonally dominant families have factors a lower-precision product
    cannot move (PERF.md, PR 24)."""
    g = r.standard_normal((n, m), dtype=np.float32)
    a = g @ g.T
    a *= np.float32(1.0 / m)
    a[np.arange(n), np.arange(n)] += np.float32(delta)
    return a


def uniform_sizes(count, lo, hi):
    """`count` sizes at the stratified quantiles (i + 1/2)/count of the
    uniform law on [lo, hi] (MAGMA's vbatched testers draw each size
    uniformly up to N), ascending: the same multiset for every seed."""
    return [int(lo + (hi - lo) * (i + 0.5) / count) for i in range(count)]


def exponential_gaps(count, rate):
    """`count` inter-arrival gaps at the stratified quantiles of
    Exp(rate), ascending: shuffled by the seed they are a Poisson-like
    arrival stream whose length and burstiness no seed changes."""
    return [-math.log(1.0 - (i + 0.5) / count) / rate
            for i in range(count)]
