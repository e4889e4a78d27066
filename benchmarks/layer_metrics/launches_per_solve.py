"""Program launches of one in-core solve (lib/readers.py)."""

from benchmarks.lib.readers import launches_per_solve as compute  # noqa: F401
