"""Operations and bytes a singular value decomposition with both sets
of vectors NEEDS, from its shape (lib/opcount.py counts the square
solves and could not be edited by the PR that added kind `svd`). f32
words.

The count is Golub and Van Loan's ("Matrix Computations", 4th ed.,
figure 8.6.1) for the Golub-Reinsch SVD with U, S and V of an m x n
matrix: 4 m^2 n + 8 m n^2 + 9 n^3, which is 21 n^3 for the square
matrix of this kind. It is the work the problem needs by the
customary dense route (bidiagonalisation, QR iteration, both
back-transformations), whatever implements the solve: a QDWH-SVD does
a polar iteration and a whole spectral divide and conquer, several
times that, so the share falls when a route does more."""


def svd(n, word=4):
    """21 n^3 flops; A in, U, s and Vh out."""
    return 21.0 * n ** 3, float(word) * (3 * n * n + n)


COUNTS = {"svd": svd}
