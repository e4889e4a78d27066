"""Device time of the first lo solve and the refinement over that of
all programs in the traced mixed-precision solve (lib/mixedtrace.py)."""

from benchmarks.lib.mixedtrace import refine_busy_share as compute  # noqa: F401
