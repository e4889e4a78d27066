#!/usr/bin/env python3
"""Control (a) of a kind-`svd` configuration's tolerance
(tools/control.py knows kinds `solve` and `serve` and could not be
edited): the plain reference (lib/plainref_svd.py) put in the
program's place on the inputs the cell makes from the same seeds, in
f32 and with its matrix products at `high` (three bfloat16 passes),
the precision below the configuration's. Host arithmetic only: it
needs no chip and holds none. One JSON line per reading: the numbers
`check()` compares.

    python benchmarks/tools/svd_control.py --config <name> \
        --seeds 1 2 3 [--n 512 1024 2048]

The textbook route's QR steps are a Python loop over rotations (some
4 n^2 of them, each two rows of n): a quarter of an hour at n=2048 and
hours at the cell's 8192, so `--n` reads the control at smaller sizes
of the same law, for the trend. Control (b), the program's own
`highest` products bound at `high` on the chip, is `tools/control.py
--program incore-svd --seconds 0`, which serves any kind.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import gen, plainref, plainref_svd, svdgen    # noqa: E402
from benchmarks.run import load_json, load_module                # noqa: E402


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--n", type=int, nargs="+")
    args = p.parse_args(argv)
    cfg = load_json(os.path.join(ROOT, "benchmarks", "configs",
                                 args.config + ".json"))
    kind = load_module("kinds", cfg["kind"])
    for n in args.n or [cfg["n"]]:
        for seed in args.seeds:
            a, s_ref = svdgen.geo_general(gen.rng(seed, "solve"), n,
                                          cfg["matrix"]["cond"])
            for label, mm in (("f32", plainref.matmul_f32),
                              ("bf16x3", plainref.matmul_bf16x3)):
                t0 = time.perf_counter()
                u, s, vh = plainref_svd.SOLVERS[cfg["routine"]](a, mm)
                print(json.dumps({
                    "config": cfg["name"], "n": n, "seed": seed,
                    "products": label,
                    **kind.grade(a, u, s, vh, s_ref, float(s_ref[0])),
                    "seconds": time.perf_counter() - t0}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
