"""Bytes the stream engine staged host to device (`ooc.h2d_bytes`,
counted over the whole window) per solve, in GB: a count, repeats
exactly."""


def compute(run):
    b, n = run["counters"].get("ooc.h2d_bytes"), run["records"].get("solves")
    if not b or not n:
        return None
    return b / n / 1e9
