"""Device time of the full-size split's programs over that of all
programs in the traced symmetric eigensolve (lib/heevtrace.py)."""

from benchmarks.lib.heevtrace import root_busy_share as compute  # noqa: F401
