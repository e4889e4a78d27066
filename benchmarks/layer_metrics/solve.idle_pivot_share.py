"""Percent of the device's idle time in the traced in-core solve during
which the host was dispatching row-permutation bookkeeping
(`getrf::pivots` of a step, `getrf::reorder` after the last)."""

from benchmarks.lib import hostspans


def compute(run):
    return hostspans.idle_cover(run, ["getrf::pivots", "getrf::reorder"])
