"""Program launches (`XLA Modules` events) of the traced least-squares
solve (lib/readers.py)."""

from benchmarks.lib.readers import launches_per_solve as compute  # noqa: F401
