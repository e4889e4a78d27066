"""`refine.ir.iters` over `refine.ir.calls` in the window
(lib/mixedtrace.py)."""

from benchmarks.lib.mixedtrace import refine_sweeps_per_solve as compute  # noqa: F401
