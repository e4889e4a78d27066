"""Seconds per solve inside `grid::place` spans (bus records over the
whole window): the placement of A and B, each open until every chip's
shard is ready (unlike `solve.upload_s`, which times a hand-over)."""


def compute(run):
    s, n = run["spans"].get("grid::place"), run["records"].get("solves")
    if not s or not n:
        return None
    return s / n
