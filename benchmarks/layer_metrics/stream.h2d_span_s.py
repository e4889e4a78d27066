"""Seconds inside `ooc::h2d` staging spans per solve, summed over the
threads that stage (thread-seconds on the host's clock: it can exceed
the wall, and is never a share of it)."""


def compute(run):
    s, n = run["spans"].get("ooc::h2d"), run["records"].get("solves")
    if not s or not n:
        return None
    return s / n
