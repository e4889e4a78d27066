"""Percent of the device's idle time in the traced streamed solve with no
span of lib/hostspans.py's table open (root driver spans not
counted)."""

from benchmarks.lib.hostspans import idle_uncovered as compute  # noqa: F401
