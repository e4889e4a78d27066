"""`heev.polar_iters` over `heev.splits` in the window
(lib/heevtrace.py)."""

from benchmarks.lib.heevtrace import polar_iters_per_split as compute  # noqa: F401
