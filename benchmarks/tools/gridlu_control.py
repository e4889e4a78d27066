#!/usr/bin/env python3
"""The readings the limits of kind `gridlu` are set from, and its
controls (builder's tool; `benchmarks/README-gridlu.md`). One JSON
line per seed and variant with the numbers `check()` compares.

On the host (numpy only; needs no chip and holds none), the plain
reference (lib/plainref_gridlu.py) put in the program's place on the
same inputs from the same seeds:

    python benchmarks/tools/gridlu_control.py --config grid2x2-gesv-n49152 \
        --seeds 1 2 3 --control ref i iii [--n 32768] [--rehearse]

  ref  the reference in f32: its own numbers, and the element growth
       the configuration's `reference_growth` is read from
  i    its products at `high` (three bfloat16 passes,
       `plainref.matmul_bf16x3`): the precision below the one stated
  iii  its row exchanges switched off: LU without pivoting

Each control makes the system from the seed, factors it WHERE IT LIES
and makes it again for the grading, so a control at the cell's size
holds one 9.66 GB matrix (some 11 GB in all) for tens of minutes.

On the chips, the program itself, each seed's system made as the cell
makes it and solved once (the warm-up's answer, graded), all seeds in
one process:

    python benchmarks/tools/gridlu_control.py --cell grid-gesv \
        --seeds .. [--lowered] [--rehearse]

  --lowered  control (ii): every `dot_general` the program binds at
             `highest` bound at `high` (tools/control.py); lowered
             programs are new programs, so unset
             JAX_COMPILATION_CACHE_DIR for it
"""

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import plainref, plainref_gridlu        # noqa: E402
from benchmarks.run import load_json, load_module           # noqa: E402

HOST = {"ref": (plainref.matmul_f32, True),
        "i": (plainref.matmul_bf16x3, True),
        "iii": (plainref.matmul_f32, False)}


def say(**kv):
    print(json.dumps(kv, default=str), flush=True)


def on_host(cfg, seeds, controls):
    kind = load_module("kinds", "gridlu")
    for seed in seeds:
        for name in controls:
            mm, pivot = HOST[name]
            cell = kind.Cell(cfg, {}, seed, system=kind._Host)
            rows, cols = cell.rows
            a, b = cell.sys.a, cell.sys.b
            top = float(np.abs(a).max())
            t0 = time.perf_counter()
            (lu, ipiv), x = plainref_gridlu.gesv(a, b, mm, pivot,
                                                 inplace=True)
            took = time.perf_counter() - t0
            factor = (lu[rows], lu[:, cols], ipiv)
            growth = plainref_gridlu.growth(np.full((1, 1), top), lu)
            del lu, a, cell
            gc.collect()
            cell = kind.Cell(cfg, {}, seed, system=kind._Host)
            say(config=cfg["name"], n=cfg["n"], seed=seed, control=name,
                **cell.grade(x, factor), growth=growth, seconds=took)
            del cell
            gc.collect()


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config")
    p.add_argument("--cell")
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--control", nargs="+", choices=sorted(HOST),
                   default=["ref", "i", "iii"])
    p.add_argument("--n", type=int)
    p.add_argument("--growth", type=float,
                   help="reference_growth to grade with (default: the "
                        "configuration's)")
    p.add_argument("--lowered", action="store_true")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    if args.cell:
        from benchmarks.tools import control, seed_readings
        if args.lowered:
            control.lower_program_products()
        return seed_readings.read(
            args.cell, args.seeds, 0.0, args.rehearse,
            label="high" if args.lowered else "highest")
    cfg = load_json(os.path.join(ROOT, "benchmarks", "configs",
                                 args.config + ".json"))
    if args.rehearse:
        cfg = {**cfg, **cfg.get("rehearsal", {})}
    if args.n:
        cfg = {**cfg, "n": args.n}
    if args.growth:
        cfg = {**cfg, "reference_growth": args.growth}
    on_host(cfg, args.seeds, args.control)
    return 0


if __name__ == "__main__":
    sys.exit(main())
