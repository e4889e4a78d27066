"""Operations and bytes a solve NEEDS, from its shapes (not the
compiler's cost model of one program, and not what a route happens to
recompute). f32 words."""


def gesv(n, nrhs, word=4):
    """LU with partial pivoting + two triangular solves: 2n^3/3 +
    2 n^2 r flops; A read once and the factor written once is already
    generous, the count keeps the least: A in, B in, X out."""
    return (2.0 * n ** 3 / 3.0 + 2.0 * n ** 2 * nrhs,
            float(word) * (n ** 2 + 2 * n * nrhs))


def posv(n, nrhs, word=4):
    """Cholesky + two triangular solves: n^3/3 + 2 n^2 r flops."""
    return (n ** 3 / 3.0 + 2.0 * n ** 2 * nrhs,
            float(word) * (n ** 2 + 2 * n * nrhs))


COUNTS = {"gesv": gesv, "posv": posv, "posv_ooc": posv}


def roofline_seconds(flops, nbytes, peak):
    """The least time the chip could take, and which bound sets it."""
    tf = flops / peak["flops_per_s"]
    tb = nbytes / peak["bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
