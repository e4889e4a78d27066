"""Mean `batch::fetch` in the traced slice: the blocking copy of one
group's results to the host (it waits for the device), ms."""

from benchmarks.lib import hostspans


def compute(run):
    return hostspans.span_mean_ms(run, "batch::fetch")
