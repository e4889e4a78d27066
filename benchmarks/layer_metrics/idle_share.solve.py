"""Device idle share of the traced in-core solve (lib/readers.py)."""

from benchmarks.lib.readers import idle_share as compute  # noqa: F401
