"""Self time of the collective operations over device-busy time in the
traced grid solve, mean of the chips (lib/gridtrace.py)."""

from benchmarks.lib.gridtrace import collective_share as compute  # noqa: F401
