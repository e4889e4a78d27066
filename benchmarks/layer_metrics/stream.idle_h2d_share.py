"""Percent of the device's idle time in the traced streamed solve during
which some thread was inside `ooc::h2d` (copy and hand-over of a
panel). Overlaps `stream.idle_stage_wait_share` where the main thread
waits for the very upload."""

from benchmarks.lib import hostspans


def compute(run):
    return hostspans.idle_cover(run, ["ooc::h2d"])
