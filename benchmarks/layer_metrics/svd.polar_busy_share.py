"""Device time of the launch `svd::polar` dispatched over that of all
programs in the traced singular value decomposition (lib/svdtrace.py)."""

from benchmarks.lib.svdtrace import polar_busy_share as compute  # noqa: F401
