"""Seconds per solve inside `grid::place` spans (bus records over the
whole window): the placement of A and B, each open until every chip's
shard is ready (lib/uploadtrace.py)."""

from benchmarks.lib import uploadtrace


def compute(run):
    return uploadtrace.span_s_per_solve(run, "grid::place")
