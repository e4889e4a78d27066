"""Bytes handed from the host to the grid's chips (`grid.h2d_bytes`,
counted over the whole window) per solve, in GB: the operands exactly,
4 (n^2 + n nrhs) bytes; more means a second copy crossed the link."""

from benchmarks.lib import gridlutrace


def compute(run):
    return gridlutrace.counter_per_solve(run, "grid.h2d_bytes", 1e9)
