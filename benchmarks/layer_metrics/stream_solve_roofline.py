"""The streamed solve's share of its roofline (lib/readers.py)."""

from benchmarks.lib.readers import solve_roofline as compute  # noqa: F401
