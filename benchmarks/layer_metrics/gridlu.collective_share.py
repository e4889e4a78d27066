"""Self time of the collective operations over device-busy time in the
traced grid LU solve, mean of the chips (lib/gridtrace.py); the row
exchange's all-reduce is among them."""

from benchmarks.lib.gridtrace import collective_share as compute  # noqa: F401
