"""Device time of the lo factor's programs over that of all programs
in the traced mixed-precision solve (lib/mixedtrace.py)."""

from benchmarks.lib.mixedtrace import factor_busy_share as compute  # noqa: F401
