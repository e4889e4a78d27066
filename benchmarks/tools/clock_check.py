#!/usr/bin/env python3
"""Do the xplane's host planes and device planes share a clock?
(builder's tool, on the chip; PERF.md has the reading)

    python benchmarks/tools/clock_check.py --ping 200
    python benchmarks/tools/clock_check.py --xplane <file> --root gesv

Prints the bracket the runtime's own events put on (device clock -
host clock): from above, `lib/hostspans.clock_offset_ns` (no execution
starts before its enqueue began; paired by `run_id`), which the reader
corrects by; from below, where the xplane holds as many
`tpu::System::Execute=>Done` events as executions, the largest
(execution end - its Done event), paired in order. Then, on the
corrected clock, the program's own spans: each `--root` span must
open before the first device operation inside it, and each
`batch::fetch`, which blocks on the device, must end after the last
operation that began under it.

`--ping` records a trace of its own: N times a small program is
launched and waited for inside one `clock::ping` annotation, which is
held to both. `--xplane` reads a traced run's file instead.
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


def summary(us):
    if not us:
        return None
    return {"n": len(us), "min_us": min(us),
            "median_us": statistics.median(us), "max_us": max(us)}


DONE = "tpu::System::Execute=>Done"


def done_side_ns(pd):
    """Largest (execution end - its Done event's start), the two lists
    paired in order; None unless they are equally long."""
    from benchmarks.lib import hostspans, reduce_trace
    done = sorted(e[0] for e in hostspans.host_events(pd, {DONE}))
    ends = sorted(float(e.start_ns) + float(e.duration_ns)
                  for p in pd.planes
                  if p.name.startswith(reduce_trace.DEVICE_PREFIX)
                  for ln in p.lines if ln.name == reduce_trace.MODULES
                  for e in ln.events)
    if not done or len(done) != len(ends):
        return None
    return max(e - d for e, d in zip(ends, done))


def margins(pd, opens, blocks, offset_ns):
    """On the corrected clock. Per span of `opens`: first device
    operation at or after the span's start, minus that start. Per span
    of `blocks`: the span's end minus the end of the last operation
    that started inside it. Microseconds; negative means the planes
    still disagree."""
    from benchmarks.lib import hostspans
    ops = sorted((s - offset_ns, e - offset_ns)
                 for plane in hostspans.device_ops(pd) for s, e in plane)
    lead, lag = [], []
    for s, e, name, _ in sorted(hostspans.host_events(
            pd, set(opens) | set(blocks))):
        inside = [op for op in ops if s <= op[0] <= e]
        if not inside:
            continue
        if name in opens:
            lead.append((inside[0][0] - s) / 1e3)
        if name in blocks:
            lag.append((e - max(op[1] for op in inside)) / 1e3)
    return lead, lag


def ping(count):
    import jax
    import jax.numpy as jnp
    from benchmarks.lib import reduce_trace
    from benchmarks.lib.tracer import Tracer

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    tr = Tracer(os.path.join(ROOT, ".bench_trace"))
    tr.start()
    for _ in range(count):
        with jax.profiler.TraceAnnotation("clock::ping"):
            f(x).block_until_ready()
        time.sleep(0.002)
    tr.stop()
    return reduce_trace.load(tr.xplane())


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--ping", type=int, default=0)
    p.add_argument("--xplane")
    p.add_argument("--root", action="append", default=[])
    args = p.parse_args()
    from benchmarks.lib import hostspans, reduce_trace
    if args.ping:
        import jax
        pd = ping(args.ping)
        out = {"check": "ping", "device": jax.devices()[0].device_kind}
    else:
        pd = reduce_trace.load(args.xplane)
        out = {"check": "xplane", "roots": args.root}
    offset = hostspans.clock_offset_ns(pd)
    below = done_side_ns(pd)
    lead, lag = margins(pd, args.root + ["clock::ping"],
                        ["batch::fetch", "clock::ping"], offset)
    out.update(clock_offset_us=offset / 1e3,
               done_side_bound_us=None if below is None else below / 1e3,
               op_start_after_span_start=summary(lead),
               span_end_after_op_end=summary(lag))
    print(json.dumps(out), flush=True)
    return 0 if min(lead + lag, default=0.0) >= 0 else 1


if __name__ == "__main__":
    sys.exit(main())
