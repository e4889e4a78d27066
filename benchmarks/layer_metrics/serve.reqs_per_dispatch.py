"""Requests per batched dispatch over the window (`batch.requests` /
`batch.dispatches`): what the coalescing window bought."""


def compute(run):
    c = run["counters"]
    if not c.get("batch.dispatches"):
        return None
    return c.get("batch.requests", 0) / c["batch.dispatches"]
