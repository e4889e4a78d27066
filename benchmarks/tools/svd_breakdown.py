#!/usr/bin/env python3
"""The traced singular value decomposition, taken apart (builder's
tool; PERF.md section 5 for `incore-svd` is written from it):

    python benchmarks/tools/svd_breakdown.py [--xplane <file>]

Reads the newest xplane under `.bench_trace` (the one the last
`run.py --workload incore-svd --trace 1` left) unless given one. One
JSON line: the clock correction, the device's busy and idle seconds in
the solve, per span of lib/svdtrace.py its count, the seconds it was
open and the idle seconds under it, the eigensolver's `heev::split`
spans by bucket (count and true sizes), the device's seconds by step
(the polar's launch, the eigensolver's programs, all) and by compiled
program (`XLA Modules`; a program's name ends in its bucket's size).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import heevtrace, hostspans, reduce_trace, svdtrace  # noqa: E402,E501
from benchmarks.lib.tracer import Tracer                        # noqa: E402


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--xplane")
    args = p.parse_args()
    path = args.xplane or Tracer(os.path.join(ROOT, ".bench_trace")).xplane()
    pd = reduce_trace.load(path)
    ops = hostspans.device_ops(pd)
    sl = svdtrace.slice_of(pd)
    buckets = {}
    for _, _, name, args_ in svdtrace.host_events(pd):
        if name == "heev::split":
            b = buckets.setdefault(int(args_["bucket"]), [])
            b.append(int(args_["size"]))
    programs = heevtrace.modules(pd)
    print(json.dumps({
        "xplane": path, "clock_offset_us": sl.offset_ns / 1e3,
        "busy_s": sum(reduce_trace.union_ns(ev)[0] for ev in ops) / 1e9,
        "idle_s": sl.idle_ns / 1e9,
        "idle_pieces_over_1ms": [
            [(s - gaps[0][0]) / 1e9, (e - s) / 1e9]
            for gaps in sl.idle[:1] for s, e in gaps if e - s > 1e6],
        "spans": {name: {"n": len(sl.spans[name]),
                         "open_s": sum(sl.durations(name)) / 1e9,
                         "idle_s": sl.covered_ns([name]) / 1e9}
                  for name in sorted(sl.spans)},
        "splits_by_bucket": {b: {"n": len(v), "sizes": sorted(v)}
                             for b, v in sorted(buckets.items())},
        "busy_by_step": svdtrace.busy_by_step(svdtrace.launches(pd)),
        "launches": sum(n for n, _ in programs.values()),
        "programs": [[k, n, s] for k, (n, s) in
                     sorted(programs.items(), key=lambda kv: -kv[1][1])]}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
