"""Share of the rows the window's splits worked on that were padding
(`heev.split_rows_padded`, `heev.split_rows_true`; lib/heevtrace.py)."""

from benchmarks.lib.heevtrace import pad_rows_share as compute  # noqa: F401
