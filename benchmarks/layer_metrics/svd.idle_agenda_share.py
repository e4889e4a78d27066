"""Share of the device's idle time in the traced singular value
decomposition under a read of the host's: `heev::agenda` (a split's
sizes) or `svd::agenda` (the polar's flags) (lib/svdtrace.py)."""

from benchmarks.lib.svdtrace import idle_agenda_share as compute  # noqa: F401
