"""Percent of the device's idle time in the traced slice of serving with
no `batch::flush` open: the idle of a queue with nothing to do."""

from benchmarks.lib import hostspans


def compute(run):
    busy = hostspans.idle_cover(run, ["batch::flush"])
    return None if busy is None else 100.0 - busy
