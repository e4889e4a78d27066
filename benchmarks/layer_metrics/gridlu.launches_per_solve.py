"""Program launches of one grid LU solve, on one chip's plane
(lib/gridtrace.py)."""

from benchmarks.lib.gridtrace import launches_per_solve as compute  # noqa: F401
