#!/usr/bin/env python3
"""What the obs bus costs when it is on (builder's tool, on the chip):
one cell's window with `obs.enable()` and NO profiler, to set beside a
`run.py --trace 0` of the same seed in the same call.

    python benchmarks/tools/bus_cost.py --workload <cell> --seed <n> \
        --seconds <s>

Prints the cell's end-to-end metrics with the bus on, the spans it
published by name and per solve or request, what the ring dropped, and
the cost of one span (bus record plus annotation) against the off
state's, timed over 100,000 empty spans after the window.
"""

import argparse
import collections
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run         # noqa: E402


def span_cost_us(obs, n=100_000):
    t0 = time.perf_counter()
    for k in range(n):
        with obs.span("cost::probe", cat="probe", k=k):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg, mix = bench_run.resolve(bench, args.workload, args.rehearse)
    opened = bench_run.open_device(cell["chips"], args.rehearse,
                                   who="bus_cost.py")
    if opened is None:
        return 2
    from slate_tpu import obs
    kind = bench_run.load_module("kinds", cfg["kind"])
    c = kind.setup(cfg, mix, args.seed)
    try:
        c.warm()
        obs.enable()
        obs.metrics.reset()
        obs.clear()
        records = c.window(args.seconds, None)
        spans = collections.Counter(
            e.name for e in obs.bus_events() if e.ph == obs.events.PH_SPAN)
        dropped = obs.events.dropped()
        on_us = span_cost_us(obs)
        obs.disable()
        obs.clear()
        off_us = span_cost_us(obs)
        verdict = c.check()
    finally:
        if hasattr(c, "close"):
            c.close()
    units = records.get("solves") or records.get("attempted")
    print(json.dumps({
        "workload": cell["name"], "bus": "on", "profiler": "off",
        "correct": bool(verdict["correct"]), "end_to_end": c.end_to_end(),
        "units": units, "spans": sum(spans.values()),
        "spans_per_unit": sum(spans.values()) / units,
        "by_name": dict(spans.most_common()), "ring_dropped": dropped,
        "span_cost_us": {"on": on_us, "off": off_us},
        "device": opened[1][0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
