"""Device idle share of the traced grid LU solve, mean of the chips
(lib/readers.py)."""

from benchmarks.lib.readers import idle_share as compute  # noqa: F401
