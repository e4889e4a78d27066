"""Splits of the spectral divide and conquer that ran in the ROOT's
bucket, per solve of the window: the root's own and every lopsided
split's larger child that outgrew the ladder (`spectral_dc._bucket_of`)
and paid for n rows again, 1.2 s each at n=8192 where everything under
the root's bucket together is 0.7 s. The library counter
`heev.full_size_splits` (raised in the agenda beside
`heev.split_rows_padded` when a split's bucket is n; PR 43) over the
window's `heev.solves`, or `svd.solves` where the eigensolver runs
inside `st.svd`. A balanced tree reads 1; the two cells' decaying
spectra read 3 at the median of the diagonal (ledger, PR 42: 8192,
5824 and 4709 rows; 8192, 6217 and 4487) and 2 with the shift taken
from an estimate of the block's spectral distribution. A program
without the counter (a commit before PR 43) is left out."""


def compute(run):
    c = run["counters"]
    full = c.get("heev.full_size_splits")
    solves = c.get("heev.solves") or c.get("svd.solves")
    if not full or not solves:
        return None
    return full / solves
