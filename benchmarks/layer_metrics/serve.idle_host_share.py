"""Percent of the device's idle time in the traced slice of serving
during which the flusher was stacking a group or resolving its
tickets (`batch::stack`, `batch::resolve`)."""

from benchmarks.lib import hostspans


def compute(run):
    return hostspans.idle_cover(run, ["batch::stack", "batch::resolve"])
