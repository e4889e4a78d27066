#!/usr/bin/env python3
"""The staging layer's spans and counters by hand (builder's tool, on
the chip; PERF.md, PR 36, has the readings):

    python benchmarks/tools/upload_probe.py --read [--xplane <file>] \
        [--root gesv]
    python benchmarks/tools/upload_probe.py --calibrate
    python benchmarks/tools/upload_probe.py --record gesv gels heev
    python benchmarks/tools/upload_probe.py --strip <in.pb> <out.pb>

`--read`: of the newest xplane under `.bench_trace` (the one the last
`run.py --trace 1` left) unless given one: the clock's bracket from
both sides (lib/clock2.py) beside the one-sided bound, each beacon's
own bracket, the idle seconds of the in-core slice and those under
`matrix::h2d_ready` (lib/uploadtrace.py) at the bracket's middle, and
the share at its two ends (what the clock can move it by), and every
`*_ready` span with what it was queued by.

`--calibrate`: what this host says of a first touch. The fresh 537 MB
copy that tools/pack_probe.py times (a (32768, 4096) column slice of
a C-ordered f32 matrix through `np.ascontiguousarray`) under a
`resident=` span, its `touched_bytes` against the buffer's bytes, then
the same copy into the same buffer again; beside them the thread's
minor faults over each (`getrusage`, which the chip's sealed host
reports as 0); and what a `resident=` span costs beside a plain one,
over 20,000 empty spans.

`--record`: the small xplanes benchmarks/tests keeps. One small solve
of each routine named, its matrix 16 MiB so that the constructor's
upload launches a clock beacon, bus on, under the benchmark's tracer;
written to chiprun_out/beacon-<routine>.xplane.pb with what `--read`
sees in it.

`--strip`: such a recording cut down to what the readers name, for
the repository to keep (a small `heev` is 17 MB as recorded): of the
host planes the program's spans, the runtime's `DoEnqueueProgram` and
`=>Done` events; of the device planes the `XLA Modules` line (the
readers take it where a plane has no `XLA Ops`); metadata nothing kept
refers to is dropped. Needs `xplane_pb2`, which TensorFlow ships: run
it off the chip.
"""

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import (clock2, hostspans, reduce_trace,  # noqa: E402
                            uploadtrace)
from benchmarks.lib.tracer import Tracer                  # noqa: E402

READY = ("matrix::h2d_ready", "ooc::h2d_ready")


def say(**kv):
    print(json.dumps(kv), flush=True)


def read(path, root):
    pd = reduce_trace.load(path)
    out = {"xplane": path, "root": root,
           "one_sided_us": hostspans.clock_offset_ns(pd) / 1e3,
           "beacons_us": [[b / 1e3, a / 1e3] for b, a in clock2.beacons(pd)],
           "ready": [{"name": n, "open_ms": (e - s) / 1e6, **args}
                     for s, e, n, args in sorted(
                         hostspans.host_events(pd, set(READY)))]}
    got = uploadtrace.read(pd, root)
    if got is not None:
        sl, (below, above) = got
        ends = [uploadtrace.UploadSlice(
            hostspans.device_ops(pd), hostspans.host_events(
                pd, {root, "matrix::h2d", uploadtrace.READY}), at, root)
            .cover((uploadtrace.READY,)) for at in (below, above)]
        out.update(below_us=below / 1e3, above_us=above / 1e3,
                   bracket_us=(above - below) / 1e3,
                   offset_us=sl.offset_ns / 1e3, idle_s=sl.idle_ns / 1e9,
                   idle_under_ready_s=sl.covered_ns(
                       (uploadtrace.READY,)) / 1e9,
                   idle_upload_share=sl.cover((uploadtrace.READY,)),
                   share_at_below_and_above=ends)
    say(**out)


def span_cost_us(obs, n=20_000, **kw):
    t0 = time.perf_counter()
    for k in range(n):
        with obs.span("cost::probe", cat="probe", k=k, **kw):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def calibrate():
    import resource
    from slate_tpu import obs

    def minflt():
        return resource.getrusage(resource.RUSAGE_THREAD).ru_minflt

    n, w = 32768, 4096
    a = np.ones((n, 2 * w), np.float32)         # touched
    panel = a[:, :w]
    obs.enable(beacon=False)
    for way in ("fresh", "again"):
        t0, f0 = time.perf_counter(), minflt()
        with obs.span("probe::pack",
                      resident="probe.pack_touched_bytes") as sp:
            if way == "fresh":
                buf = np.ascontiguousarray(panel)
            else:
                np.copyto(buf, panel)
        say(way=way, bytes=buf.nbytes, seconds=time.perf_counter() - t0,
            touched_bytes=sp.args.get("touched_bytes"),
            minflt=minflt() - f0, pages_4k=buf.nbytes // 4096)
    say(span_cost_us={"plain": span_cost_us(obs), "resident": span_cost_us(
        obs, resident="probe.cost_touched_bytes")})
    obs.disable()
    say(span_cost_us={"off": span_cost_us(obs)})


def record(routine):
    import jax
    import slate_tpu as st
    from slate_tpu import obs
    from slate_tpu.obs import events
    r = np.random.default_rng(36)
    if routine == "gesv":
        a = r.standard_normal((2048, 2048)).astype(np.float32)
        b = r.standard_normal((2048, 4)).astype(np.float32)

        def solve():
            return st.gesv(st.Matrix(a, mb=256), st.Matrix(b, mb=256))[1].data
    elif routine == "gels":
        a = r.standard_normal((4096, 1024)).astype(np.float32)
        b = r.standard_normal((4096, 4)).astype(np.float32)

        def solve():
            return st.gels(st.Matrix(a, mb=256), st.Matrix(b, mb=256)).data
    else:
        g = r.standard_normal((2048, 2048)).astype(np.float32)
        a = (g + g.T) / 2

        def solve():
            w, v = st.heev(st.HermitianMatrix(st.Uplo.Lower, a, mb=256))
            return w, v.data
    jax.block_until_ready(solve())              # compile
    obs.enable()
    tr = Tracer(os.path.join(ROOT, ".bench_trace"))
    tr.start()
    jax.block_until_ready(solve())
    events.flush_ready(10.0)
    tr.stop()
    obs.disable()
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    kept = os.path.join(out, "beacon-%s.xplane.pb" % routine)
    shutil.copy(tr.xplane(), kept)
    say(recorded=routine, bytes=os.path.getsize(kept),
        device=jax.devices()[0].device_kind, reduced=tr.reduce())
    read(kept, routine)


KEEP_HOST = {hostspans.ENQUEUE, "tpu::System::Execute=>Done",
             clock2.BEACON_SPAN, "matrix::h2d", "gesv", "gels", "heev",
             "getrf", "getrs", *READY}


def strip(src, dst):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space, out = xplane_pb2.XSpace(), xplane_pb2.XSpace()
    with open(src, "rb") as fh:
        space.ParseFromString(fh.read())
    for plane in space.planes:
        host = plane.name.startswith(hostspans.HOST_PREFIX)
        if not host and not plane.name.startswith(reduce_trace.DEVICE_PREFIX):
            continue
        new = xplane_pb2.XPlane(id=plane.id, name=plane.name)
        for ln in plane.lines:
            evs = [e for e in ln.events
                   if plane.event_metadata[e.metadata_id].name in KEEP_HOST] \
                if host else ln.events if ln.name == reduce_trace.MODULES \
                else []
            if evs:
                kept = new.lines.add()
                kept.CopyFrom(ln)
                del kept.events[:]
                kept.events.extend(evs)
        # the metadata the kept events refer to, and no other
        for ln in new.lines:
            for e in ln.events:
                meta = new.event_metadata[e.metadata_id]
                meta.id, meta.name = e.metadata_id, \
                    plane.event_metadata[e.metadata_id].name
                for st in e.stats:
                    for k in (st.metadata_id, st.ref_value):
                        if k in plane.stat_metadata:
                            new.stat_metadata[k].CopyFrom(
                                plane.stat_metadata[k])
        if new.lines:
            out.planes.append(new)
    with open(dst, "wb") as fh:
        fh.write(out.SerializeToString())
    say(stripped=src, to=dst, bytes=[os.path.getsize(src),
                                     os.path.getsize(dst)])


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--read", action="store_true")
    p.add_argument("--xplane")
    p.add_argument("--root", default="gesv")
    p.add_argument("--calibrate", action="store_true")
    p.add_argument("--record", nargs="*", default=[],
                   choices=("gesv", "gels", "heev"))
    p.add_argument("--strip", nargs=2, metavar=("IN", "OUT"))
    args = p.parse_args()
    if args.strip:
        strip(*args.strip)
    if args.read:
        read(args.xplane or Tracer(os.path.join(
            ROOT, ".bench_trace")).xplane(), args.root)
    if args.calibrate:
        calibrate()
    for routine in args.record:
        record(routine)
    return 0


if __name__ == "__main__":
    sys.exit(main())
