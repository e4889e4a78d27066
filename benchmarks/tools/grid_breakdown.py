#!/usr/bin/env python3
"""Where the time of a traced grid solve went, chip by chip (builder's
tool; PERF.md section 5 is written from it):

    python benchmarks/tools/grid_breakdown.py [--xplane <file>]

Reads the newest xplane under `.bench_trace` (the one the last
`run.py --workload grid-posv --trace 1` left) unless given one. One
JSON line: per chip the busy seconds, the launches, the self seconds of
the collective operations by opcode and of the ten operations that
took most; the chips' idle seconds in the solve and, per span of
lib/gridtrace.py's table, its count, the seconds it was open and the
idle seconds during which it was open (summed over the chips).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import gridtrace, reduce_trace      # noqa: E402
from benchmarks.lib.tracer import Tracer                # noqa: E402


def chips(pd):
    out = []
    for p in pd.planes:
        lines = {ln.name: ln for ln in p.lines}
        if not p.name.startswith(reduce_trace.DEVICE_PREFIX) \
                or reduce_trace.OPS not in lines:
            continue
        line = lines[reduce_trace.OPS]
        by_code = reduce_trace.self_times(
            reduce_trace._events(line, gridtrace.opcode))
        by_name = reduce_trace.self_times(
            reduce_trace._events(line, reduce_trace.short_name))
        out.append({
            "plane": p.name,
            "collectives_s": {op: sec for op, sec in sorted(by_code.items())
                              if gridtrace.is_collective(op)},
            "top": reduce_trace._top(by_name)})
    return out


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--xplane")
    args = p.parse_args()
    path = args.xplane or Tracer(os.path.join(ROOT, ".bench_trace")).xplane()
    pd = reduce_trace.load(path)
    t = gridtrace.read(pd)
    sl = t["slice"]
    rows = {name: {"n": len(sl.spans[name]),
                   "open_s": sum(sl.durations(name)) / 1e9,
                   "idle_s": sl.covered_ns([name]) / 1e9,
                   "root": name in gridtrace.ROOTS}
            for name in sorted(sl.spans)}
    leaves = [n for n in sl.spans if n not in gridtrace.ROOTS]
    print(json.dumps({
        "xplane": path, "clock_offset_us": sl.offset_ns / 1e3,
        "busy_s": t["busy_s"], "launches": t["launches"],
        "collective_s": t["collective_s"], "chips": chips(pd),
        "idle_s": sl.idle_ns / 1e9,
        "uncovered_s": (sl.idle_ns - sl.covered_ns(leaves)) / 1e9,
        "spans": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
