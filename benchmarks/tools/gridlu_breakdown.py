#!/usr/bin/env python3
"""Where the time of a traced grid LU solve went, chip by chip
(builder's tool; PERF.md section 5 is written from it):

    python benchmarks/tools/gridlu_breakdown.py [--xplane <file>] [--nb 512]

Reads the newest xplane under `.bench_trace` (the one the last
`run.py --workload grid-gesv --trace 1` left) unless given one. One
JSON line: per chip the busy seconds and the self seconds by phase
(lib/gridlutrace.py: panel, exchange, other collectives, the rest);
the forty operations that took most over all chips, each with its
phase, seconds and count (what the phases were told apart by: read it
before trusting them); the chips' idle seconds in the solve and, per
span of lib/gridlutrace.py's table, its count, the seconds it was open
and the idle seconds during which it was open (summed over the chips).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import gridlutrace, reduce_trace    # noqa: E402
from benchmarks.lib.tracer import Tracer                # noqa: E402


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--xplane")
    p.add_argument("--nb", type=int, default=512)
    args = p.parse_args()
    path = args.xplane or Tracer(os.path.join(ROOT, ".bench_trace")).xplane()
    table = {}
    t = gridlutrace.read(reduce_trace.load(path), args.nb, table)
    sl = t["slice"]
    rows = {name: {"n": len(sl.spans[name]),
                   "open_s": sum(sl.durations(name)) / 1e9,
                   "idle_s": sl.covered_ns([name]) / 1e9,
                   "root": name in gridlutrace.ROOTS}
            for name in sorted(sl.spans)}
    leaves = [n for n in sl.spans if n not in gridlutrace.ROOTS]
    top = sorted(table.items(), key=lambda kv: -kv[1][0])[:40]
    print(json.dumps({
        "xplane": path, "clock_offset_us": sl.offset_ns / 1e3,
        "busy_s": t["busy_s"], "phase_s": t["phase_s"],
        "top": [[ph, name, sec, count]
                for (ph, name), (sec, count) in top],
        "idle_s": sl.idle_ns / 1e9,
        "uncovered_s": (sl.idle_ns - sl.covered_ns(leaves)) / 1e9,
        "spans": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
