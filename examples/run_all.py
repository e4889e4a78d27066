#!/usr/bin/env python
"""Run every example as a smoke test (reference examples/run_tests.py,
CTest mpirun role — here: single process, all jax devices)."""

import pathlib
import runpy
import sys

here = pathlib.Path(__file__).parent
sys.path.insert(0, str(here.parent))

from slate_tpu.utils import compile_cache  # noqa: E402

# runs on whatever backend jax finds (JAX_PLATFORMS=cpu for the CPU
# tier); no probe and no fallback — a missing backend raises
compile_cache.enable()

failed = []
for ex in sorted(here.glob("ex*.py")):
    print(f"=== {ex.name} ===")
    try:
        runpy.run_path(str(ex), run_name="__main__")
    except Exception as e:   # noqa: BLE001
        print(f"FAILED: {e}")
        failed.append(ex.name)
print("\n" + ("All examples passed" if not failed
              else f"FAILED: {failed}"))
sys.exit(1 if failed else 0)
