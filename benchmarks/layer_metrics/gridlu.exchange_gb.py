"""GB of rows the factor program's steps exchange per solve
(`grid.lu_exchange_bytes`, from n, nb and the stage plan, counted
where `lu.getrf` dispatches the form: 2 nb rows a step, of the stage's
square and past the first stage of the result too); a permutation of
the whole matrix a step, which the form before PR 49 was, reads
`grid.lu_exchange_bytes_full`: 928 GB at n=49152."""

from benchmarks.lib import gridlutrace


def compute(run):
    return gridlutrace.counter_per_solve(run, "grid.lu_exchange_bytes", 1e9)
