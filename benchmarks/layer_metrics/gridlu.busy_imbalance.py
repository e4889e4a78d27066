"""Largest less smallest device-busy time of the grid's chips over
their mean, in the traced grid LU solve (lib/gridtrace.py)."""

from benchmarks.lib.gridtrace import busy_imbalance as compute  # noqa: F401
