"""Percent of the device's idle time in the traced streamed solve during
which the host was allocating and zero-filling the factor's buffer
(`ooc::alloc`): before the first panel is staged."""

from benchmarks.lib import hostspans


def compute(run):
    return hostspans.idle_cover(run, ["ooc::alloc"])
