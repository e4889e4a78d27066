"""Percent of the chips' idle time in the traced grid LU solve during
which `grid::place` was open: the device waiting for the host's
upload of A and B (lib/gridlutrace.py)."""

from benchmarks.lib import gridlutrace


def compute(run):
    return gridlutrace.idle_cover(run, ["grid::place"])
