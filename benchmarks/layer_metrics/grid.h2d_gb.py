"""Bytes handed from the host to the grid's chips (`grid.h2d_bytes`,
counted over the whole window) per solve, in GB: the operands exactly,
4 (n^2 + n nrhs) bytes; more means a second copy crossed the link."""


def compute(run):
    b = run["counters"].get("grid.h2d_bytes")
    n = run["records"].get("solves")
    if not b or not n:
        return None
    return b / n / 1e9
