"""The mixed-precision solve's share of its roofline: HPL-MxP's flops
over the one-pass bf16 peak (lib/mixedtrace.py, lib/mixedcount.py)."""

from benchmarks.lib.mixedtrace import solve_roofline as compute  # noqa: F401
