"""Device idle share of the traced mixed-precision solve
(lib/readers.py)."""

from benchmarks.lib.readers import idle_share as compute  # noqa: F401
