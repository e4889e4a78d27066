"""The lo factor's programs against 2/3 n^3 at the one-pass bf16 peak
(lib/mixedtrace.py, lib/mixedcount.py)."""

from benchmarks.lib.mixedtrace import factor_roofline as compute  # noqa: F401
