"""Operations and bytes a symmetric eigendecomposition with all
vectors NEEDS, from its shape (lib/opcount.py counts the square solves
and could not be edited by the PR that added kind `heev`). f32 words.

The count is the customary one of the tridiagonalisation route:
4 n^3 / 3 for the reduction to tridiagonal form, some 4 n^3 / 3 for a
divide and conquer tridiagonal solve with vectors at worst, 2 n^3 for
the back-transformation, rounded up to 9 n^3 with the QR-iteration
solver's accumulation; it is the figure Nakatsukasa and Higham
("Stable and efficient spectral divide and conquer algorithms for the
symmetric eigenvalue decomposition and the SVD", SIAM J. Sci. Comput.
35(3), 2013, table 5.1 and section 5) set beside QDWH-eig's 27 n^3.
It is the work the problem needs by the cheapest known dense route,
whatever implements the solve: the share falls when a route does
more."""


def heev(n, word=4):
    """9 n^3 flops; A in, w and V out."""
    return 9.0 * n ** 3, float(word) * (2 * n * n + n)


COUNTS = {"heev": heev}
