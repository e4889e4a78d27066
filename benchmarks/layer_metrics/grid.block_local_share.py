"""Percent of the block steps of the scan forms dispatched under a
grid whose blocks were read and written on the chips that own them
(`grid.block_steps_local` over local + `grid.block_steps_masked`,
counted over the whole window where `chol.potrf` and `chol.potrs`
dispatch the forms: 96 steps for the factor and 192 for the two
sweeps of a solve at n=49152, nb=512). A block that can straddle two
chips is picked by a mask over the chip's whole share of the matrix
instead, 2.4 GB rewritten to deliver 50 MB (ledger, PR 31: 4.3 s of
6.9 busy a chip). A program that counts no block steps publishes
neither counter and the metric is left out."""


def compute(run):
    c = run["counters"]
    local = c.get("grid.block_steps_local", 0)
    masked = c.get("grid.block_steps_masked", 0)
    if not local + masked:
        return None
    return 100.0 * local / (local + masked)
