"""`gels.refactors` over `gels.solves` in the window: the share of
solves whose first route was abandoned (lib/lstsqtrace.py)."""

from benchmarks.lib.lstsqtrace import refactor_share as compute  # noqa: F401
