"""Share of the device's idle time in the traced symmetric eigensolve
under `heev::agenda`, the host's read of a split's sizes
(lib/heevtrace.py)."""

from benchmarks.lib.heevtrace import idle_agenda_share as compute  # noqa: F401
