"""Percent of the bytes the mesh's placement staged by a host-side
copy that went into an already touched slot of its staging ring
(`grid.stage_reuse_bytes` over reuse + `grid.stage_fresh_bytes`,
counted over the whole window): a copy into fresh pages runs at a
twelfth of the speed on the v5e host (PERF.md, PR 26), and four
strided blocks handed to the runtime whole reached a 2x2 grid at 1.8
GB/s (ledger, PR 27). Blocks that are contiguous as they lie go
uncopied and are in neither counter. A program whose placement has no
ring publishes neither and the metric is left out."""


def compute(run):
    c = run["counters"]
    reuse = c.get("grid.stage_reuse_bytes", 0)
    fresh = c.get("grid.stage_fresh_bytes", 0)
    if not reuse + fresh:
        return None
    return 100.0 * reuse / (reuse + fresh)
