"""Longest `serve::submit` in the traced slice: the most one call held
its sender, ms."""

from benchmarks.lib import hostspans


def compute(run):
    return hostspans.span_max_ms(run, "serve::submit")
