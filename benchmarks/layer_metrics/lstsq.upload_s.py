"""Seconds inside `matrix::h2d` spans in the traced least-squares
solve: the hand-over of the host arrays A (1 GiB) and B that `solve_s`
contains (the transfer itself is not waited for; lib/lstsqtrace.py)."""

from benchmarks.lib import lstsqtrace


def compute(run):
    return lstsqtrace.span_sum_s(run, "matrix::h2d")
