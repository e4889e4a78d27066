"""The symmetric eigensolve's share of its roofline (lib/heevtrace.py,
lib/heevcount.py)."""

from benchmarks.lib.heevtrace import solve_roofline as compute  # noqa: F401
