"""Device seconds of the panel kernel (the block loop nested in the
stage loops of the factor program, and all under it) over device-busy
seconds in the traced grid LU solve, mean of the chips
(lib/gridlutrace.py)."""

from benchmarks.lib import gridlutrace


def compute(run):
    return gridlutrace.phase_share(run, "panel")
