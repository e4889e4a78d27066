"""The panel kernel against the roofline of ITS flops and bytes at the
heights it ran, on one chip: every chip factors every panel whole
(lib/gridlutrace.py, lib/gridlucount.py)."""

from benchmarks.lib.gridlutrace import panel_roofline as compute  # noqa: F401
