"""Operations and bytes a mixed-precision dense solve NEEDS, from its
shape (lib/opcount.py counts the f32 solves and could not be edited by
the PR that added kind `mixed`).

The flops are HPL-MxP's count (hpl-mxp.org: the benchmark credits
2/3 n^3 + 3/2 n^2 whatever the implementation does: the refinement's
residuals and lo solves are not credited). The bytes are what one
solve must move whatever implements it: the f32 A read once, the lo
factor written once in `lo_word` bytes an entry, b and x. The peak
they are held to is the chip's ONE-PASS bf16 rate (lib/peaks.py): the
first roofline here that is not capped at a sixth of it."""


def gesv_mixed(n, nrhs=1, word=4, lo_word=2):
    """(flops, bytes) of one solve."""
    return (2.0 * n ** 3 / 3.0 + 1.5 * n * n,
            float(word * n * n + lo_word * n * n + 2 * word * n * nrhs))


def factor(n):
    """Flops of the lo factorization alone."""
    return 2.0 * n ** 3 / 3.0


COUNTS = {"gesv_mixed": gesv_mixed}
