"""Seconds inside `matrix::h2d` spans in the traced in-core solve: the
hand-over of the host arrays A and B that `solve_s` contains (the
transfer itself is not waited for)."""

from benchmarks.lib import hostspans


def compute(run):
    return hostspans.span_sum_s(run, "matrix::h2d")
