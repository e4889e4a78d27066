"""`heev.polar_iters` over `heev.splits` in the window of a singular
value decomposition cell, whose eigensolver runs inside `svd::eig`
(lib/svdtrace.py, lib/heevtrace.py)."""

from benchmarks.lib.svdtrace import eig_polar_iters_per_split as compute  # noqa: F401
