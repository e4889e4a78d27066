"""Mean over the window's requests of the wait from `submit` to the
start of the flush that took the request
(`batch.queue_wait_seconds`, observed per ticket)."""


def compute(run):
    h = run["histograms"].get("batch.queue_wait_seconds")
    if not h or not h["count"]:
        return None
    return 1e3 * h["mean"]
