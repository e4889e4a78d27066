"""Share of the device's idle time in the traced mixed-precision solve
under the host's one read, `gesv_mixed::verdict` (lib/mixedtrace.py)."""

from benchmarks.lib.mixedtrace import idle_verdict_share as compute  # noqa: F401
