"""Program launches of one streamed solve (lib/readers.py)."""

from benchmarks.lib.readers import launches_per_solve as compute  # noqa: F401
