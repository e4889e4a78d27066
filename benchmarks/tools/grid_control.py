#!/usr/bin/env python3
"""Control (a) of a kind-`grid` configuration's tolerance
(tools/control.py knows kinds `solve` and `serve`): the plain reference
(lib/plainref.py) in the program's place on the inputs the cell makes
from the same seeds, once in f32 and once with its matrix products at
`high` (three bfloat16 passes), the precision below the configuration's.
Prints, per seed and precision, the numbers `check()` compares. Host
arithmetic only: it needs no chip and holds none.

    python benchmarks/tools/grid_control.py --config grid2x2-posv-n49152 \
        --seeds 1 2 3 [--n 16384]

`--n` reads the control at a smaller order where the configuration's
own (1.2e14 host flops a product precision at n=49152) does not fit a
session; control (b), the program's own products lowered on the chip,
is `tools/control.py --program <cell>`, which serves every kind.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import gen, plainref, refcheck   # noqa: E402
from benchmarks.run import load_json                 # noqa: E402


def control(cfg, seed, mm):
    """The cell's system and sampled rows (kinds/grid.py makes them
    from these streams), solved by the reference."""
    r = gen.rng(seed, "solve")
    n = cfg["n"]
    a, b = gen.spd_gram(r, n), gen.rhs(r, n, cfg["nrhs"])
    rows = refcheck.factor_sample(n, gen.rng(seed, "sample"))
    fac = []
    x = plainref.SOLVERS[cfg["routine"]](a, b, mm, factor=fac)
    return {"scaled_residual_max": refcheck.hpl_resid_blocked(a, x, b, n),
            "factor_residual_rms": refcheck.factor_resid(
                a[np.ix_(rows, rows)], fac[0][rows], rows)}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--n", type=int)
    p.add_argument("--products", nargs="+", default=["f32", "bf16x3"])
    args = p.parse_args(argv)
    cfg = load_json(os.path.join(ROOT, "benchmarks", "configs",
                                 args.config + ".json"))
    if args.n:
        cfg = {**cfg, "n": args.n}
    mms = {"f32": plainref.matmul_f32, "bf16x3": plainref.matmul_bf16x3}
    for seed in args.seeds:
        for label in args.products:
            t0 = time.perf_counter()
            out = control(cfg, seed, mms[label])
            print(json.dumps({"config": cfg["name"], "n": cfg["n"],
                              "seed": seed, "products": label, **out,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)


if __name__ == "__main__":
    sys.exit(main())
