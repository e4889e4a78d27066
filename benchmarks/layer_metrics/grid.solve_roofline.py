"""The grid solve's share of the roofline of all its chips
(lib/gridtrace.py)."""

from benchmarks.lib.gridtrace import solve_roofline as compute  # noqa: F401
