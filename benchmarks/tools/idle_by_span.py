#!/usr/bin/env python3
"""What the host was doing while the device sat idle, by span, in
seconds (builder's tool; PERF.md section 5 is written from it):

    python benchmarks/tools/idle_by_span.py [--xplane <file>]

Reads the newest xplane under `.bench_trace` (the one the last
`run.py --trace 1` left) unless given one. One JSON line: what the
device's clock was ahead of the host's by and was corrected for, the
device's idle seconds between operations, and per span of lib/hostspans.py's
table its count, the seconds it was open (summed over threads) and the
idle seconds during which it was open on some thread. Rows overlap
where threads do; `uncovered_s` is the idle time under no row but a
root driver span's.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import hostspans, reduce_trace      # noqa: E402
from benchmarks.lib.tracer import Tracer                # noqa: E402


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--xplane")
    args = p.parse_args()
    path = args.xplane or Tracer(os.path.join(ROOT, ".bench_trace")).xplane()
    pd = reduce_trace.load(path)
    sl = hostspans.Slice(hostspans.device_ops(pd), hostspans.host_events(pd),
                         hostspans.clock_offset_ns(pd))
    rows = {name: {"n": len(sl.spans[name]),
                   "open_s": sum(sl.durations(name)) / 1e9,
                   "idle_s": sl.covered_ns([name]) / 1e9,
                   "root": name in hostspans.ROOTS}
            for name in sorted(sl.spans)}
    leaves = [n for n in sl.spans if n not in hostspans.ROOTS]
    print(json.dumps({
        "xplane": path, "clock_offset_us": sl.offset_ns / 1e3,
        "idle_s": sl.idle_ns / 1e9,
        "uncovered_s": (sl.idle_ns - sl.covered_ns(leaves)) / 1e9,
        "spans": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
