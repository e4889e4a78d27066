"""The grid LU solve's share of the roofline of all its chips: the
2/3 n^3 + 2 n^2 r flops a general solve NEEDS (lib/opcount.py `gesv`)
over the four chips' summed peak, over their mean busy seconds
(lib/gridtrace.py)."""

from benchmarks.lib.gridtrace import solve_roofline as compute  # noqa: F401
