#!/usr/bin/env python3
"""The traced mixed-precision solve, taken apart (builder's tool;
PERF.md section 5 for `incore-gesv-mixed` is written from it):

    python benchmarks/tools/mixed_breakdown.py [--xplane <file>]
    python benchmarks/tools/mixed_breakdown.py --record [--n 2048]

Reads the newest xplane under `.bench_trace` (the one the last
`run.py --workload incore-gesv-mixed --trace 1` left) unless given
one. One JSON line: the route on the `gesv_mixed` and `getrf` spans,
the clock correction, the device's busy and idle seconds in the
solve, per span of lib/mixedtrace.py its count, the seconds it was
open and the idle seconds under it, the device's seconds by step (the
lo factor's programs, the refinement's, all), of each panel in order
and by compiled program (`XLA Modules`).

`--record`: the small xplane benchmarks/tests keeps: one small solve
on the chip, bus on, under the benchmark's tracer, written to
chiprun_out/mixed.xplane.pb; `tools/upload_probe.py`'s `strip` with
this path's span names cuts it down off the chip (`--strip SRC DST`).
"""

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import (heevtrace, hostspans, mixedtrace,  # noqa: E402
                            reduce_trace, svdtrace)
from benchmarks.lib.tracer import Tracer                        # noqa: E402


def breakdown(path):
    pd = reduce_trace.load(path)
    ops = hostspans.device_ops(pd)
    sl = mixedtrace.slice_of(pd)
    route = {name: args for _, _, name, args in mixedtrace.host_events(pd)
             if name in (mixedtrace.ROOT, "getrf")}
    programs = heevtrace.modules(pd)
    ordered = svdtrace.launches(pd)
    return {
        "xplane": path, "route": route,
        "clock_offset_us": sl.offset_ns / 1e3,
        "busy_s": sum(reduce_trace.union_ns(ev)[0] for ev in ops) / 1e9,
        "idle_s": sl.idle_ns / 1e9,
        "idle_pieces_over_1ms": [
            [(s - gaps[0][0]) / 1e9, (e - s) / 1e9]
            for gaps in sl.idle[:1] for s, e in gaps if e - s > 1e6],
        "spans": {name: {"n": len(sl.spans[name]),
                         "open_s": sum(sl.durations(name)) / 1e9,
                         "idle_s": sl.covered_ns([name]) / 1e9}
                  for name in sorted(sl.spans)},
        "busy_by_step": mixedtrace.busy_by_step(ordered),
        "panels_s": [sec for _, name, sec in ordered
                     if name == mixedtrace.FACTOR[0]],
        "launches": sum(n for n, _ in programs.values()),
        "programs": [[k, n, s] for k, (n, s) in
                     sorted(programs.items(), key=lambda kv: -kv[1][1])]}


def record(n):
    import jax
    import numpy as np
    import slate_tpu as st
    from slate_tpu import obs
    from benchmarks.kinds import mixed
    a, b = mixed.hplmxp_system(np.random.default_rng(42), n)
    a, b = jax.device_put(a), jax.device_put(b)

    def solve():
        return st.gesv_mixed(st.Matrix(a, mb=256), st.Matrix(b, mb=256),
                             {st.Option.BlockSize: 512})[1].data
    jax.block_until_ready(solve())              # compile
    obs.enable()
    tr = Tracer(os.path.join(ROOT, ".bench_trace"))
    tr.start()
    jax.block_until_ready(solve())
    tr.stop()
    obs.disable()
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    kept = os.path.join(out, "mixed.xplane.pb")
    shutil.copy(tr.xplane(), kept)
    print(json.dumps({"recorded": kept, "bytes": os.path.getsize(kept),
                      "device": jax.devices()[0].device_kind,
                      "reduced": tr.reduce()}), flush=True)
    return kept


def strip(src, dst):
    from benchmarks.tools import upload_probe
    upload_probe.KEEP_HOST = set(upload_probe.KEEP_HOST) | set(
        mixedtrace.SPANS) | {mixedtrace.FALLBACK}
    upload_probe.strip(src, dst)


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--xplane")
    p.add_argument("--record", action="store_true")
    p.add_argument("--n", type=int, default=2048)
    p.add_argument("--strip", nargs=2, metavar=("SRC", "DST"))
    args = p.parse_args()
    if args.strip:
        strip(*args.strip)
        return 0
    path = record(args.n) if args.record else args.xplane or Tracer(
        os.path.join(ROOT, ".bench_trace")).xplane()
    print(json.dumps(breakdown(path), default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
