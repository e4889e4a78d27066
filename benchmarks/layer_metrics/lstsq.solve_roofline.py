"""The least-squares solve's share of its roofline
(lib/lstsqtrace.py, lib/lstsqcount.py)."""

from benchmarks.lib.lstsqtrace import solve_roofline as compute  # noqa: F401
