"""Percent of the device's idle time in the traced streamed solve during
which the consuming thread waited for a panel (`ooc::wait_stage`: a
pending prefetch or a synchronous upload)."""

from benchmarks.lib import hostspans


def compute(run):
    return hostspans.idle_cover(run, ["ooc::wait_stage"])
