"""GB per solve that the mesh placement touched for the first time
(`grid.pack_touched_bytes`: what the process's resident set grew by
under a host array's `matrix::h2d` inside `grid::place`, the span
around the staging threads' packs and hand-overs): near zero while the
ring's slots are reused (PR 28) and the source matrix has been
written. The count is the whole process's: nothing else runs while a
placement is open (lib/uploadtrace.touched_gb_per_solve)."""

from benchmarks.lib import uploadtrace


def compute(run):
    return uploadtrace.touched_gb_per_solve(run, "grid.pack_touched_bytes")
