"""Percent of the device's idle time in the traced streamed solve during
which a panel was written back to the host or waited on
(`ooc::d2h`, `ooc::writeback`, `ooc::wait_write`)."""

from benchmarks.lib import hostspans


def compute(run):
    return hostspans.idle_cover(
        run, ["ooc::d2h", "ooc::writeback", "ooc::wait_write"])
