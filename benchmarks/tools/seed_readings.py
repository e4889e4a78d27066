#!/usr/bin/env python3
"""The numbers `check()` compares, read on many seeds in ONE process
(one compile, one device set-up): what a tolerance's lower end is set
from. Per seed: the cell is made from the seed, warmed (for kind
`solve` the warm-up solve is itself an answer that is graded), driven
for --seconds at the cell's own load, and graded. One JSON line per
seed; a run that is not `correct` under the limits in force is
flagged, not hidden.

    python benchmarks/tools/seed_readings.py --workload <cell> \
        --seconds 8 --seeds 1 2 3 ...
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.run import (load_json, load_module,          # noqa: E402
                            open_device, resolve)


def read(workload, seeds, seconds, rehearse=False, label=None):
    """One JSON line per seed; returns an exit code."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg, mix = resolve(bench, workload, rehearse)
    if open_device(cell["chips"], rehearse, "seed_readings") is None:
        return 2
    kind = load_module("kinds", cfg["kind"])
    for k, seed in enumerate(seeds):
        c = kind.setup(cfg, mix, seed)
        try:
            # kind `serve` warms its programs once a process; a solve's
            # warm-up is itself an answer and is made for every seed
            c.warm(**({"programs": False} if k and cfg["kind"] == "serve"
                      else {}))
            if seconds > 0:
                c.window(seconds, None)
            v = c.check()
        finally:
            if hasattr(c, "close"):
                c.close()
        print(json.dumps({"workload": workload, "seed": seed,
                          **({"products": label} if label else {}),
                          **{k: val for k, val in v.items()
                             if k != "compared"},
                          **{name: value for name, value, _ in
                             v["compared"]}}), flush=True)
    return 0


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    return read(args.workload, args.seeds, args.seconds, args.rehearse)


if __name__ == "__main__":
    sys.exit(main())
