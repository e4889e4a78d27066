"""Seconds per solve, over the window, during which an upload through
the matrix constructors was in flight: bus seconds inside
`matrix::h2d_ready`, which the `obs-ready` thread holds open from the
hand-over until the array is on the chip. lib/uploadtrace.py."""

from benchmarks.lib import uploadtrace


def compute(run):
    return uploadtrace.span_s_per_solve(run, uploadtrace.READY)
