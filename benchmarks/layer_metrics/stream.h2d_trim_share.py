"""Percent of a streamed solve's factor-panel bytes that a trimmed
upload left on the host (`ooc.h2d_trimmed_bytes` over trimmed +
`ooc.h2d_bytes`, counted over the whole window): a lower-Cholesky
factor panel is staged from its diagonal block down and zero-embedded
on the device, so the zeros above the block are neither faulted in
and packed on the host nor sent (PERF.md, PR 34). A program that
stages every panel at full height publishes no such counter and the
metric is left out."""


def compute(run):
    c = run["counters"]
    trimmed = c.get("ooc.h2d_trimmed_bytes", 0)
    if not trimmed:
        return None
    return 100.0 * trimmed / (trimmed + c.get("ooc.h2d_bytes", 0))
