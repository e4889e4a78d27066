"""Percent of the bytes the stream engine staged by a host-side copy
that went into an already touched slot of its staging ring
(`ooc.h2d_stage_reuse_bytes` over reuse + `ooc.h2d_stage_fresh_bytes`,
counted over the whole window): a copy into fresh pages runs at a
twelfth of the speed on the v5e host (PERF.md, PR 26). A program
without the ring publishes neither counter and the metric is left
out."""


def compute(run):
    c = run["counters"]
    reuse = c.get("ooc.h2d_stage_reuse_bytes", 0)
    fresh = c.get("ooc.h2d_stage_fresh_bytes", 0)
    if not reuse + fresh:
        return None
    return 100.0 * reuse / (reuse + fresh)
