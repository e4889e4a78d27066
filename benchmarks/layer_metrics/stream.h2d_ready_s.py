"""Seconds per solve, over the window, during which a staged panel's
upload was in flight: bus seconds inside `ooc::h2d_ready`, which the
`obs-ready` thread holds open from the hand-over until the panel is on
the chip. One thread in order, so the spans never overlap: link-busy
seconds, where `stream.h2d_span_s` sums the staging threads' copies
and hand-overs. lib/uploadtrace.py."""

from benchmarks.lib import uploadtrace


def compute(run):
    return uploadtrace.span_s_per_solve(run, "ooc::h2d_ready")
