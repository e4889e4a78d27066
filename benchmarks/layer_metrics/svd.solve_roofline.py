"""The singular value decomposition's share of its roofline
(lib/svdtrace.py, lib/svdcount.py)."""

from benchmarks.lib.svdtrace import solve_roofline as compute  # noqa: F401
