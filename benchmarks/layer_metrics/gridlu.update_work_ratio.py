"""FLOPs of the trailing updates the staged scan form of the LU
dispatched under a grid over the 2 n^3 / 3 a factorization needs
(`grid.lu_update_flops` over `grid.lu_update_flops_needed`, counted
over the whole window where `lu.getrf` dispatches the form; an update
is the whole of a stage's square). One stage over the whole matrix at
each of nt steps is 3.0 (2 n^3); S even stages are 3 (1/S) sum
(j/S)^2: 1.41 at four. A program that counts no update FLOPs
publishes neither counter and the metric is left out."""


def compute(run):
    c = run["counters"]
    needed = c.get("grid.lu_update_flops_needed", 0)
    if not needed:
        return None
    return c.get("grid.lu_update_flops", 0) / needed
