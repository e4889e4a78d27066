"""What the grid Cholesky's factor program costs by the number of
stages its scan form runs in (PR 41; run by hand on the chips, never
in tier-1):

    python3 -m tools.stage_probe [--n 49152] [--nb 512] [--grid 2x2]
                                 [--stages 1,4,6,8] [--reps 3] [--trace]

For each count it sets `blocked.CHOL_SCAN_STAGES`, compiles
`chol._grid_potrf_programs(grid)[1]` for an (n, n) f32 matrix spread
as P('p','q') (made on the mesh: a random symmetric matrix with n on
its diagonal, so the factor exists and the host sends nothing), runs
it once warm and `--reps` times, and prints the compile seconds, the
compiler's count of the program's temporaries, the walls to
`block_until_ready` and the worst of `|L L^T - A|` over 64 sampled
rows (on the host) against the largest of those of A. One stage is the form as it was
before PR 41. `--trace` runs each count once more under the profiler
and adds, per chip, the busy seconds and the self seconds of the
operations by opcode and of the twelve that took most (the benchmark's
own reduction, `benchmarks/lib/reduce_trace.py`). One JSON object on
the last line, and the same in
`chiprun_out/stage_probe.json`. `--n 768 --nb 8` on four CPU devices
finds faults.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=49152)
    ap.add_argument("--nb", type=int, default=512)
    ap.add_argument("--grid", default="2x2")
    ap.add_argument("--stages", default="1,4,6,8")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--trace", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import slate_tpu as st
    from slate_tpu.linalg import blocked, chol

    n, nb = args.n, args.nb
    p, q = map(int, args.grid.split("x"))
    grid = st.make_grid(p, q, devices=jax.devices()[:p * q])
    spread = grid.matrix_sharding()

    def spd(key):
        u = jax.random.uniform(key, (n, n), jnp.float32, -1.0, 1.0)
        return 0.5 * (u + u.T) + n * jnp.eye(n, dtype=jnp.float32)

    a = jax.jit(spd, out_shardings=spread)(jax.random.key(41))
    rows = np.sort(np.random.default_rng(41).choice(n, 64, replace=False))

    from benchmarks.kinds.grid import rows_to_host
    a_rows = rows_to_host(a, rows)[:, rows].astype(np.float64)

    def apart(l):
        # the sampled rows of tril(L), chip by chip, against A's
        low = rows_to_host(l, rows).astype(np.float64)
        low[np.arange(n)[None, :] > rows[:, None]] = 0.0
        return float(np.abs(low @ low.T - a_rows).max()
                     / np.abs(a_rows).max())

    def traced(run):
        from benchmarks.lib import gridtrace, reduce_trace
        from benchmarks.lib.tracer import Tracer
        tr = Tracer(os.path.join(ROOT, ".bench_trace", "stage_probe"))
        tr.start()
        try:
            jax.block_until_ready(run())
        finally:
            tr.stop()
        chips = []
        for plane in reduce_trace.load(tr.xplane()).planes:
            lines = {ln.name: ln for ln in plane.lines}
            if not plane.name.startswith(reduce_trace.DEVICE_PREFIX) \
                    or reduce_trace.OPS not in lines:
                continue
            ops = lines[reduce_trace.OPS]
            by_name = reduce_trace.self_times(
                reduce_trace._events(ops, reduce_trace.short_name))
            by_code = reduce_trace.self_times(
                reduce_trace._events(ops, gridtrace.opcode))
            busy, _ = reduce_trace.union_ns(
                [(s, e) for s, e, _ in reduce_trace._events(ops)])
            chips.append({
                "plane": plane.name, "busy_s": busy / 1e9,
                "by_opcode": dict(sorted(by_code.items(),
                                         key=lambda kv: -kv[1])[:8]),
                "top": sorted(by_name.items(), key=lambda kv: -kv[1])[:12]})
        return chips

    out = {"n": n, "nb": nb, "grid": args.grid,
           "device": jax.devices()[0].device_kind, "stages": {}}
    for count in map(int, args.stages.split(",")):
        blocked.CHOL_SCAN_STAGES = count
        chol._grid_potrf_programs.cache_clear()
        factor = chol._grid_potrf_programs(grid)[1]
        t0 = time.perf_counter()
        compiled = factor.lower(a, nb, lookahead=1).compile()
        compile_s = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        jax.block_until_ready(compiled(a))
        walls, l = [], None
        for _ in range(args.reps):
            del l
            t0 = time.perf_counter()
            l = jax.block_until_ready(compiled(a))
            walls.append(time.perf_counter() - t0)
        row = {"plan": blocked.chol_scan_stages(n, nb, grid),
               "work_ratio": blocked.chol_scan_update_flops(n, nb, grid)
               / (n ** 3 / 3),
               "compile_s": compile_s,
               "temp_gb": mem.temp_size_in_bytes / 1e9,
               "factor_s": walls, "apart": apart(l)}
        del l
        if args.trace:
            row["chips"] = traced(lambda: compiled(a))
        out["stages"][count] = row
        print(json.dumps({count: row}), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "stage_probe.json"),
              "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
