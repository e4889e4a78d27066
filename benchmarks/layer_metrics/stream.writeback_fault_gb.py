"""GB per solve that became resident while the writer copied a panel
out (`ooc.d2h_touched_bytes`: what the process's resident set grew by
under `ooc::d2h`, the span around the chunk threads of one
write-back): the factor's host buffer, mapped by `np.zeros` and first
written here, and the fetched chunks' own fresh arrays, which are
still mapped when the span closes. On the chip's host the first
panel's strided write maps the whole buffer, 4.29 GB, and the chunks
add a panel's bytes in every write-back: 6.0 a solve (PERF.md, PR 36).
The count is the whole process's, so it also holds what other threads
map meanwhile (lib/uploadtrace.touched_gb_per_solve)."""

from benchmarks.lib import uploadtrace


def compute(run):
    return uploadtrace.touched_gb_per_solve(run, "ooc.d2h_touched_bytes")
