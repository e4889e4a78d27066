"""Device idle share of the traced singular value decomposition
(lib/readers.py)."""

from benchmarks.lib.readers import idle_share as compute  # noqa: F401
