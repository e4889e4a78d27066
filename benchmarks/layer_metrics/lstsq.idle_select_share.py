"""Share of the device's idle time in the traced least-squares solve
under `gels::select` and any abandoned first route
(lib/lstsqtrace.py)."""

from benchmarks.lib.lstsqtrace import idle_select_share as compute  # noqa: F401
