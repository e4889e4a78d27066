#!/usr/bin/env python3
"""Find, once, the highest request rate a `serve` cell sustains: rates
doubling from 25/s until the backlog when the window closes exceeds
one second of arrivals (or a request fails), then one bisection step
between the last rate that held and the first that did not. Then, to
see what bound a rate would need, --trials windows each at 0.8, 0.6
and 0.4 of that rate, arrivals reshuffled. One process, one warm-up,
one JSON line per window. The traffic mix then fixes its rate as a
number (benchmarks/README.md).

    python benchmarks/tools/knee_sweep.py --workload serve-steady \
        --seed 1 --seconds 20 --trials 3
"""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import loadgen                           # noqa: E402
from benchmarks.run import (load_json, load_module,          # noqa: E402
                            open_device, resolve)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--trials", type=int, default=3)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry, cfg, mix = resolve(bench, args.workload, args.rehearse)
    if open_device(entry["chips"], args.rehearse, "knee_sweep") is None:
        return 2
    cell = load_module("kinds", cfg["kind"]).setup(cfg, mix, args.seed)
    t0 = time.perf_counter()
    cell.warm()
    print(json.dumps({"warm_s": time.perf_counter() - t0}), flush=True)

    def trial(rate):
        rec = cell.offer(rate, args.seconds)
        ms = [1e3 * v for v in rec["lat_s"]]
        held = rec["backlog_at_close"] <= rate and not rec["failed"]
        print(json.dumps({
            "rate_per_s": rate, "attempted": rec["attempted"],
            "failed": rec["failed"],
            "backlog_at_close": rec["backlog_at_close"], "held": held,
            "p50_ms": statistics.median(ms),
            "p95_ms": loadgen.percentile(ms, 95),
            "late_p95_ms": 1e3 * loadgen.percentile(rec["late_s"], 95),
            "longest_stall": rec["longest_stall"]}), flush=True)
        return held

    try:
        rate, good, bad = 25.0, None, None
        while bad is None and rate <= 6400:
            if trial(rate):
                good, rate = rate, rate * 2
            else:
                bad = rate
        if good is not None and bad is not None and trial((good + bad) / 2):
            good = (good + bad) / 2
        print(json.dumps({"sustained_per_s": good,
                          "first_rate_not_held": bad}), flush=True)
        for share in (0.8, 0.6, 0.4) if good else ():
            for k in range(args.trials):
                cell.seed = args.seed + 1 + k
                trial(round(share * good, 1))
    finally:
        cell.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
