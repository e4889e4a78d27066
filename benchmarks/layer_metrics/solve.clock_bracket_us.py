"""Width of the bracket the clock beacons of the traced solve put on
(device clock - host clock), microseconds: what
`solve.idle_upload_share` can be off by (lib/clock2.py)."""

from benchmarks.lib import uploadtrace


def compute(run):
    return uploadtrace.clock_bracket_us(run, "gesv")
