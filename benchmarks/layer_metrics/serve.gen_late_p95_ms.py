"""How late the load generator's sender ran: 95th percentile of
(sent - due), ms. A starved generator must not read as a fast
server."""

from benchmarks.lib import loadgen


def compute(run):
    late = run["records"].get("late_s")
    if not late:
        return None
    return 1e3 * loadgen.percentile(late, 95)
