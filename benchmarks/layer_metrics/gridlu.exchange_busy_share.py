"""Device seconds of the row exchange (the operations of the factor
program that hold the 2 nb rows a step moves: their gather, their
all-reduce along `p`, their scatter) over device-busy seconds in the
traced grid LU solve, mean of the chips (lib/gridlutrace.py)."""

from benchmarks.lib import gridlutrace


def compute(run):
    return gridlutrace.phase_share(run, "exchange")
