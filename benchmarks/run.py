#!/usr/bin/env python3
"""Run ONE cell of BENCHMARK.json once, in one process that holds the
chip:

    python benchmarks/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up (imports, inputs from the seed, warm-up, compile or cache
retrieval) is timed as `setup_s`; then the cell's kind drives its
window for `--seconds`; then every answer is checked on the host.
Earlier lines are free text; the LAST line is one JSON object with
`correct`, `attempted`, `failed`, `metrics`, `device` (and
`breakdown` with `--trace 1`). `--trace 0` reports the cell's
end-to-end metrics, `--trace 1` its per-layer metrics, taken with the
profiler around a slice of the window and the obs counters on.

Nothing here knows a cell: the workload names a configuration
(`configs/<file>`, whose `kind` names `kinds/<kind>.py`) and a traffic
mix (`traffic/<mix>.json`); each per-layer metric is
`layer_metrics/<name>.py`. See benchmarks/README.md.

Exits non-zero, printing no result line, when JAX finds no TPU or
fewer chips than the cell asks for, and when anything compiled inside
the window. `--rehearse` (builder only, never a number) takes sizes
from the `rehearsal` blocks and skips the TPU check.
"""

import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(**kv):
    print(json.dumps(kv, default=str), flush=True)


def load_json(path):
    with open(path) as fh:
        return json.load(fh)


def load_module(directory, name):
    """`<directory>/<name>.py` under benchmarks/, found by name (a
    metric's name may hold dots, so not an import statement)."""
    path = os.path.join(HERE, directory, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError("%s names no file %s" % (name, path))
    spec = importlib.util.spec_from_file_location(
        "benchmarks_%s_%s" % (directory, name.replace(".", "_")
                              .replace("-", "_")), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(bench, workload, rehearse):
    """The cell's workload entry, configuration and traffic mix, with
    the `rehearsal` blocks folded in under --rehearse."""
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit("no workload %r in BENCHMARK.json; have %s"
                         % (workload, [w["name"] for w in
                                       bench["workloads"]]))
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(os.path.join(ROOT, entry["file"]))
    mix = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    if rehearse:
        cfg = {**cfg, **cfg.get("rehearsal", {})}
        mix = {**mix, **mix.get("rehearsal", {})}
    return cell, cfg, mix


def open_device(chips, rehearse, who="benchmarks/run.py"):
    """Place the compile cache and look for the chips. Returns
    (cache directory, devices), or None, having said why on stderr,
    when JAX finds no TPU or fewer chips than asked for (--rehearse
    takes whatever JAX has)."""
    from benchmarks.lib import cache, peaks
    cache_dir = cache.place()
    import jax
    devs = jax.devices()
    if not rehearse:
        if devs[0].platform != "tpu" or len(devs) < chips:
            print("%s: needs %d TPU chip(s); jax found %d device(s) of "
                  "platform %r" % (who, chips, len(devs), devs[0].platform),
                  file=sys.stderr)
            return None
        peaks.peak(devs[0].device_kind)  # an unknown device is an error
    return cache_dir, devs


def wanted(metrics, workload):
    return [m for m in metrics
            if "workloads" not in m or workload in m["workloads"]]


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    t_start = T_START if argv is None else time.perf_counter()

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg, mix = resolve(bench, args.workload, args.rehearse)

    opened = open_device(cell["chips"], args.rehearse)
    if opened is None:
        return 2
    cache_dir, devs = opened
    dev = devs[0]
    from benchmarks.lib.compiles import Compiles
    from benchmarks.lib.tracer import Tracer
    comp = Compiles()
    kind = load_module("kinds", cfg["kind"])

    c = kind.setup(cfg, mix, args.seed)
    try:
        c.warm()
        setup_s = time.perf_counter() - t_start
        say(phase="setup", setup_s=setup_s, compile_cache_dir=cache_dir,
            **comp.since((0, 0.0, 0, 0)))

        tracer = None
        if args.trace:
            from slate_tpu import obs
            obs.enable()
            obs.metrics.reset()
            obs.clear()
            tracer = Tracer(os.path.join(ROOT, ".bench_trace"))
        s0 = comp.snap()
        records = c.window(args.seconds, tracer)
        in_window = comp.since(s0)
        say(phase="window", compiles_in_window=in_window,
            **{k: v for k, v in records.items()
               if not isinstance(v, list)})
        run = {"workload": cell["name"], "config": cfg, "mix": mix,
               "records": records, "device_kind": dev.device_kind,
               "trace": None, "counters": {}, "histograms": {}, "spans": {}}
        if args.trace:
            snap = obs.snapshot()["metrics"]
            run["counters"] = snap["counters"]
            run["histograms"] = snap["histograms"]
            for e in obs.bus_events(cat="staging"):
                run["spans"][e.name] = run["spans"].get(e.name, 0.0) + e.dur
            obs.disable()
            obs.clear()
            run["trace"] = tracer.reduce()
            say(phase="trace", xplane=tracer.xplane(), reduced=run["trace"])
        verdict = c.check()
    finally:
        if hasattr(c, "close"):
            c.close()
    for name, value, limit in verdict["compared"]:
        say(phase="check", compared=name, value=value, limit=limit,
            ok=bool(value <= limit))
    say(phase="check", **{k: v for k, v in verdict.items()
                          if k != "compared"})
    if in_window["programs"]:
        print("benchmarks/run.py: %d program(s) compiled or were fetched "
              "from the cache INSIDE the window; the warm-up is "
              "incomplete: %s" % (in_window["programs"], in_window),
              file=sys.stderr)
        return 3

    metrics = {}
    if args.trace:
        for m in wanted(bench["per_layer"], cell["name"]):
            value = load_module("layer_metrics", m["name"]).compute(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        seen = dict(c.end_to_end(), setup_s=setup_s)
        for m in wanted(bench["end_to_end"], cell["name"]):
            metrics[m["name"]] = {"value": seen[m["name"]],
                                  "unit": m["unit"]}
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devs[:cell["chips"]])
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    out = {"correct": bool(verdict["correct"]),
           "attempted": verdict["attempted"], "failed": verdict["failed"],
           "metrics": metrics, "device": device}
    if args.trace and run["trace"] is not None:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        out["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                            "idle_gaps": run["trace"]["idle_gaps"]}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
