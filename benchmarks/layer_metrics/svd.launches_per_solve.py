"""Program launches (`XLA Modules` events) of the traced singular value
decomposition: the polar, the form, four a split and one a leaf of the
eigensolver, the compose (lib/readers.py)."""

from benchmarks.lib.readers import launches_per_solve as compute  # noqa: F401
