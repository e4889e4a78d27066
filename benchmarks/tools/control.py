#!/usr/bin/env python3
"""The control of a configuration's tolerance: the plain reference
(lib/plainref.py) put in the program's place, on the same inputs from
the same seeds, once in f32 and once with its matrix products at the
precision below the one the configuration states (`high`: three
bfloat16 passes). Prints, per seed, the numbers `check()` compares.
The tolerance has to sit above the program's largest sound reading and
below the smallest `bf16x3` reading here. Host arithmetic only: it
needs no chip and holds none.

    python benchmarks/tools/control.py --config <name> --seeds 1 2 3

With --program <cell> it confirms the same limits on the chip with the
PROGRAM's own products lowered instead: every `dot_general` the
program binds at `highest` is bound at `high`, and the cell is then
made, warmed, driven for --seconds and graded like any run
(tools/seed_readings.py). XLA's native Cholesky, LU and triangular
solves keep their own arithmetic. Lowered programs are new programs:
unset JAX_COMPILATION_CACHE_DIR so that they do not fill the
machine's cache.

    python benchmarks/tools/control.py --program <cell> --seeds 1 2 3
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

from benchmarks.lib import plainref, refcheck        # noqa: E402
from benchmarks.run import load_json, load_module    # noqa: E402


def control_solve(cfg, seed, mm):
    cell = load_module("kinds", "solve").Cell(cfg, {}, seed)
    sys_, rows = cell.sys, cell.rows
    if cfg["routine"] == "gesv":
        x = plainref.lu_solve(sys_.a, sys_.b, mm)
        return {"scaled_residual_max": sys_.residual(x)}
    fac = []
    x = plainref.chol_solve(sys_.a, sys_.b, mm, factor=fac)
    return {"scaled_residual_max": sys_.residual(x),
            "factor_residual_rms": refcheck.factor_resid(
                sys_.a[np.ix_(rows, rows)], fac[0][rows], rows)}


def control_serve(cfg, seed, mm):
    serve = load_module("kinds", "serve")
    pool = serve.Cell(cfg, {}, seed).pool
    ids = serve.pick_sample(cfg, seed, range(len(pool)), pool.__getitem__)
    res = serve.grade(
        cfg, ids, pool.__getitem__,
        lambda i: plainref.SOLVERS[pool[i][0]](pool[i][1], pool[i][2], mm))
    return {"scaled_residual_max." + op: max(v) for op, v in res.items()}


def lower_program_products():
    """From here on every matrix product this process traces at
    `highest` is traced at `high` (einsum and vmapped forms too: the
    primitive's own bind is wrapped)."""
    import jax
    from jax._src.lax import lax as _lax
    hi, low = jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGH
    real = _lax.dot_general_p.bind

    def bind(*args, **kw):
        p = kw.get("precision")
        if isinstance(p, tuple) and hi in p:
            kw["precision"] = tuple(low if q == hi else q for q in p)
        return real(*args, **kw)
    _lax.dot_general_p.bind = bind


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config")
    p.add_argument("--program", metavar="CELL")
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    if args.program:
        from benchmarks.tools import seed_readings
        lower_program_products()
        return seed_readings.read(args.program, args.seeds, args.seconds,
                                  args.rehearse, label="program at high")
    cfg = load_json(os.path.join(ROOT, "benchmarks", "configs",
                                 args.config + ".json"))
    if args.rehearse:
        cfg = {**cfg, **cfg.get("rehearsal", {})}
    run = control_serve if cfg["kind"] == "serve" else control_solve
    for seed in args.seeds:
        for label, mm in (("f32", plainref.matmul_f32),
                          ("bf16x3", plainref.matmul_bf16x3)):
            t0 = time.perf_counter()
            out = run(cfg, seed, mm)
            print(json.dumps({"config": cfg["name"], "seed": seed,
                              "products": label, **out,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)


if __name__ == "__main__":
    sys.exit(main())
