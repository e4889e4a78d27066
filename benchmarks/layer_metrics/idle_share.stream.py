"""Device idle share of the traced streamed solve (lib/readers.py)."""

from benchmarks.lib.readers import idle_share as compute  # noqa: F401
