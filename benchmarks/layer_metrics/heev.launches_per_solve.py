"""Program launches (`XLA Modules` events) of the traced symmetric
eigensolve: four a split, one a leaf (lib/readers.py)."""

from benchmarks.lib.readers import launches_per_solve as compute  # noqa: F401
