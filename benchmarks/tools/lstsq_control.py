#!/usr/bin/env python3
"""The controls of a kind-`lstsq` configuration's tolerance, and the
by-hand readings of its route (tools/control.py knows kinds `solve`
and `serve` and could not be edited). One JSON line per reading.

Control (a), host arithmetic only, no chip: the plain reference
(lib/plainref_lstsq.py) in the program's place on the inputs the cell
makes from the same seeds, in f32 and with its matrix products at
`high` (three bfloat16 passes), the precision below the
configuration's:

    python benchmarks/tools/lstsq_control.py --config <name> --seeds 1 2 3

Control (b), the program's own `highest` products bound at `high` on
the chip, is `tools/control.py --program tall-gels`, which serves any
kind. Control (c) and the by-hand readings need the chip (`--cell`):

    --route cholqr   control (c): st.gels forced to MethodGels.CholQR,
                     the route the parent took on the shape alone; an
                     error raised is the reading
    --route auto | qr-fused | qr-tiled
                     the library's own choice; Householder QR with
                     geqrf's native whole-matrix route; with its
                     blocked carry route
    --well           the same call on the well-conditioned family (the
                     Gaussian factor alone, cond 1.67): the route
                     taken and the wall

Each reading is one warm-up call and `--repeat` timed calls from the
host arrays to `block_until_ready` of X, the error of the last X
against the f64 solution, and the route the `gels` and `geqrf` spans
recorded with the seconds of each phase span of the last call.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import gen, plainref                    # noqa: E402
from benchmarks.lib import plainref_lstsq                   # noqa: E402
from benchmarks.run import (load_json, load_module,         # noqa: E402
                            open_device, resolve)


def route_options(route):
    from slate_tpu.core.methods import MethodFactor, MethodGels
    from slate_tpu.core.options import Option
    return {"auto": None,
            "cholqr": {Option.MethodGels: MethodGels.CholQR},
            "qr-fused": {Option.MethodGels: MethodGels.QR,
                         Option.MethodFactor: MethodFactor.Fused},
            "qr-tiled": {Option.MethodGels: MethodGels.QR,
                         Option.MethodFactor: MethodFactor.Tiled}}[route]


def host_control(cfg, kind, seed, mm):
    system = kind.Cell(cfg, {}, seed).sys
    x = plainref_lstsq.SOLVERS[cfg["routine"]](system.a, system.b, mm)
    return kind.solution_error(
        x, kind.reference_solution(system.a, system.b))


def chip_reading(cfg, kind, seed, route, repeat):
    """Warm-up, `repeat` timed calls, the last call's spans."""
    import jax
    from slate_tpu import obs
    system = kind.Cell(cfg, {}, seed).sys
    system.opts = route_options(route)
    out = {"route_asked": route}
    try:
        system.solve()
        walls = []
        for i in range(repeat):
            if i == repeat - 1:
                obs.enable()
                obs.clear()
            t0 = time.perf_counter()
            _, X = system.solve()
            walls.append(time.perf_counter() - t0)
        evs = obs.bus_events()
        obs.disable()
        obs.clear()
        out["walls_s"] = walls
        for e in evs:
            if e.cat == "driver" and e.name in ("gels", "geqrf"):
                out[e.name] = {k: v for k, v in (e.args or {}).items()
                               if k not in ("shape", "dtype")}
        out["phase_s"] = {}
        for e in evs:
            if e.cat == "phase" or e.name == "matrix::h2d":
                out["phase_s"][e.name] = (out["phase_s"].get(e.name, 0.0)
                                          + e.dur)
        out["solution_error_max"] = kind.solution_error(
            X.to_numpy(), kind.reference_solution(system.a, system.b))
        out["memory_peak_bytes"] = (jax.devices()[0].memory_stats()
                                    or {}).get("peak_bytes_in_use")
    except Exception as exc:            # the reading of control (c)
        out["raised"] = "%s: %s" % (type(exc).__name__, str(exc)[:300])
    return out


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config")
    p.add_argument("--cell", help="a cell of BENCHMARK.json: on the chip")
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--route", nargs="+", default=["auto"],
                   choices=("auto", "cholqr", "qr-fused", "qr-tiled"))
    p.add_argument("--well", action="store_true")
    p.add_argument("--repeat", type=int, default=3)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    if args.cell:
        bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cell, cfg, _ = resolve(bench, args.cell, args.rehearse)
        if open_device(cell["chips"], args.rehearse,
                       "lstsq_control") is None:
            return 2
    else:
        cfg = load_json(os.path.join(ROOT, "benchmarks", "configs",
                                     args.config + ".json"))
        if args.rehearse:
            cfg = {**cfg, **cfg.get("rehearsal", {})}
    if args.well:
        cfg = {**cfg, "matrix": {**cfg["matrix"], "cond": None}}
    kind = load_module("kinds", cfg["kind"])
    head = {"config": cfg["name"], "m": cfg["m"], "n": cfg["n"],
            "cond": cfg["matrix"]["cond"]}
    mms = {"f32": plainref.matmul_f32, "bf16x3": plainref.matmul_bf16x3}
    for seed in args.seeds:
        if not args.cell:
            for label, mm in mms.items():
                t0 = time.perf_counter()
                e = host_control(cfg, kind, seed, mm)
                print(json.dumps({**head, "seed": seed, "products": label,
                                  "solution_error_max": e, "seconds":
                                  time.perf_counter() - t0}), flush=True)
            continue
        for route in args.route:
            print(json.dumps({**head, "seed": seed, **chip_reading(
                cfg, kind, seed, route, args.repeat)}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
