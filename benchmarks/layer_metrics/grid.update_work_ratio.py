"""FLOPs of the trailing updates the scan form of the Cholesky
dispatched under a grid over the n^3/3 a factorization needs
(`grid.update_flops` over `grid.update_flops_needed`, counted over the
whole window where `chol.potrf` dispatches the form; the updates are
full squares, so 2 is the least a stage plan could reach). One stage
over the whole matrix at each of nt steps is 6.0 (2 n^3; ledger, PR
39: 1.92 s of 2.63 busy a chip at n=49152); S even stages over the
trailing square are 6 (1/S) sum (j/S)^2: 2.81 at four. A program that
counts no update FLOPs publishes neither counter and the metric is
left out."""


def compute(run):
    c = run["counters"]
    needed = c.get("grid.update_flops_needed", 0)
    if not needed:
        return None
    return c.get("grid.update_flops", 0) / needed
