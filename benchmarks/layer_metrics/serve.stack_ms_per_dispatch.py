"""Mean `batch::stack` in the traced slice: host stacking and padding of
one group, ms."""

from benchmarks.lib import hostspans


def compute(run):
    return hostspans.span_mean_ms(run, "batch::stack")
