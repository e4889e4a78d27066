"""Percent of the chips' idle time in the traced grid solve during
which `grid::place` was open: the device waiting for the host's
upload of A and B (lib/gridtrace.py)."""

from benchmarks.lib import gridtrace


def compute(run):
    return gridtrace.idle_cover(run, ["grid::place"])
