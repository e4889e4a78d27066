"""Device time of the eigensolver's programs (`jit_dc_*`, the polar's
launch apart) over that of all programs in the traced singular value
decomposition (lib/svdtrace.py)."""

from benchmarks.lib.svdtrace import eig_busy_share as compute  # noqa: F401
