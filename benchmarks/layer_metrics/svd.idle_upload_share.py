"""Share of the device's idle time in the traced singular value
decomposition during which an upload was in flight (`matrix::h2d_ready`
open), on the two-sided clock (lib/uploadtrace.py, lib/clock2.py)."""

from benchmarks.lib import uploadtrace


def compute(run):
    return uploadtrace.idle_upload_share(run, "svd")
