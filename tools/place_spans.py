"""The mesh placement's spans in a traced run, read by hand (PR 28):

    python3 -m tools.place_spans [--xplane <file>]

From the newest xplane under `.bench_trace` (the one the last
`benchmarks/run.py --workload grid-posv --trace 1` left): per
`grid::place` its seconds and bytes and, inside it, the `grid::pack`,
`grid::put` and `grid::wait_ring` spans by device: how many, the
seconds they were open, the packed bytes and the pack's rate. One JSON
line. The benchmark's own span table (`benchmarks/lib/gridtrace.py`)
does not carry these names; a `benchmark` issue may add them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

NAMES = ("grid::place", "matrix::h2d", "grid::pack", "grid::put",
         "grid::wait_ring")


def main(argv=None):
    from benchmarks.lib import hostspans, reduce_trace
    from benchmarks.lib.tracer import Tracer
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--xplane")
    args = ap.parse_args(argv)
    path = args.xplane or Tracer(os.path.join(ROOT, ".bench_trace")).xplane()
    evs = sorted(hostspans.host_events(reduce_trace.load(path), set(NAMES)))
    out = []
    for s, e, name, stats in evs:
        if name != "grid::place":
            continue
        row = {"place_s": (e - s) / 1e9, "bytes": int(stats["bytes"]),
               "inside": {}}
        for s2, e2, n2, st2 in evs:
            if n2 in ("grid::place", "matrix::h2d") or not s <= s2 <= e:
                continue
            dev = str(st2.get("device", st2.get("on", "")))
            d = row["inside"].setdefault(n2, {}).setdefault(
                dev, {"count": 0, "seconds": 0.0, "bytes": 0})
            d["count"] += 1
            d["seconds"] += (e2 - s2) / 1e9
            d["bytes"] += int(st2.get("bytes", 0))
        for d in row["inside"].get("grid::pack", {}).values():
            d["gb_per_s"] = d["bytes"] / d["seconds"] / 1e9
        out.append(row)
    print(json.dumps({"xplane": path, "placements": out}))


if __name__ == "__main__":
    main()
