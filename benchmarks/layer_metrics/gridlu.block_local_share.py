"""Percent of the block steps of the LU's scan forms dispatched under
a grid whose blocks were read and written on the chips that own them
(`grid.lu_block_steps_local` over local + `grid.lu_block_steps_masked`,
counted over the whole window where `lu.getrf` and `lu.getrs` dispatch
the forms: 96 steps for the factor and 192 for the two sweeps of a
solve at n=49152, nb=512). A program that counts no such step
publishes neither counter and the metric is left out."""


def compute(run):
    c = run["counters"]
    local = c.get("grid.lu_block_steps_local", 0)
    masked = c.get("grid.lu_block_steps_masked", 0)
    if not local + masked:
        return None
    return 100.0 * local / (local + masked)
