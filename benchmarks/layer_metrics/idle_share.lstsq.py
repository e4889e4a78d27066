"""Device idle share of the traced least-squares solve
(lib/readers.py)."""

from benchmarks.lib.readers import idle_share as compute  # noqa: F401
