"""`svd.polar_iters` over `svd.solves` in the window
(lib/svdtrace.py)."""

from benchmarks.lib.svdtrace import polar_iters_per_solve as compute  # noqa: F401
