"""Operations and bytes a least-squares solve NEEDS, from its shapes
(lib/opcount.py counts the square solves and could not be edited by
the PR that added kind `lstsq`). f32 words."""


def gels(m, n, nrhs, word=4):
    """Householder QR of an (m, n) matrix, Q^H B and one triangular
    solve: 2 m n^2 - 2 n^3 / 3 + 4 m n r flops (the n^2 r of the
    back substitution is under a thousandth of it and left out, as
    LAPACK's count does); A in, B in, X out. What a route recomputes
    (a Gram matrix it abandons, a second pass) is not counted: the
    share falls when a route does more than this."""
    return (2.0 * m * n ** 2 - 2.0 * n ** 3 / 3.0 + 4.0 * m * n * nrhs,
            float(word) * (m * n + m * nrhs + n * nrhs))


COUNTS = {"gels": gels}
