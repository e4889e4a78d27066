"""Program launches (`XLA Modules` events) of the traced
mixed-precision solve: the demote, three a block step and one finish
of the lo factor, the first lo solve, the refinement, and the eager
operations around them (lib/readers.py)."""

from benchmarks.lib.readers import launches_per_solve as compute  # noqa: F401
