"""Tests of benchmarks/lib/clock2.py, lib/uploadtrace.py and the
per-layer metrics read through them (PR 36), on the CPU. The bracket
on planes made by hand (device waits for host, host runs ahead, no
beacon), the shares on the small solves recorded on the chip
(`tools/upload_probe.py --record`:
benchmarks/tests/data/beacon-<routine>.xplane.pb), and every new
metric found by name, moving an end-to-end metric of its cell,
computing on that data and silent on a run without its spans.
"""

import os
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import clock2, hostspans, reduce_trace, uploadtrace
from benchmarks.lib.tracer import Tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
HERE = os.path.join(ROOT, "benchmarks", "tests")
DATA = os.path.join(HERE, "data", "beacon-%s.xplane.pb")

#: the per-layer metrics PR 36 added: name -> (cell, the recorded
#: solve it computes on, or None for one read from the bus's sums)
UPLOAD_METRICS = {
    "solve.upload_ready_s": ("incore-gesv", None),
    "solve.idle_upload_share": ("incore-gesv", "gesv"),
    "solve.clock_bracket_us": ("incore-gesv", "gesv"),
    "lstsq.upload_ready_s": ("tall-gels", None),
    "lstsq.idle_upload_share": ("tall-gels", "gels"),
    "lstsq.clock_bracket_us": ("tall-gels", "gels"),
    "heev.upload_ready_s": ("incore-heev", None),
    "heev.idle_upload_share": ("incore-heev", "heev"),
    "stream.h2d_ready_s": ("stream-posv", None),
    "stream.writeback_fault_gb": ("stream-posv", None),
    "grid.pack_fault_gb": ("grid-posv", None),
}

US, MS = 1e3, 1e6


def _plane(name, lines):
    """A profile plane as the readers see one: `lines` is {line name:
    [(start ns, end ns, event name, {stat: value})]}."""
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[
            types.SimpleNamespace(name=n, start_ns=s, duration_ns=e - s,
                                  stats=list(st.items()))
            for s, e, n, st in evs])
        for ln, evs in lines.items()])


def _profile(skew, lag, beacon=True):
    """Five launches 2 ms apart, enqueued at t on the host and started
    `lag(i)` later on a device whose stamps are `skew` ahead, and one
    beacon: its span opens at 10 ms, its program starts 30 us later,
    runs 10 us, and the waiting thread wakes 60 us after that."""
    host, mods = [], []
    for i in range(5):
        t = 20 * MS + i * 2 * MS
        host.append((t, t + 20 * US, hostspans.ENQUEUE, {"run_id": i}))
        mods.append((t + lag(i) + skew, t + lag(i) + skew + 500 * US,
                     "jit_step(%d)" % i, {"run_id": i}))
    if beacon:
        s = 10 * MS
        host.append((s, s + 100 * US, clock2.BEACON_SPAN, {}))
        host.append((s + 5 * US, s + 15 * US, hostspans.ENQUEUE,
                     {"run_id": 99}))
        mods.append((s + 30 * US + skew, s + 40 * US + skew,
                     clock2.BEACON_PROGRAM + "(7)", {"run_id": 99}))
    return types.SimpleNamespace(planes=[
        _plane("/host:CPU", {"main": host}),
        _plane("/device:TPU:0", {reduce_trace.MODULES: mods})])


def test_bracket_agrees_with_the_one_sided_bound_while_the_device_waits():
    skew = -1.2 * MS
    pd = _profile(skew, lambda i: 20 * US)
    one = hostspans.clock_offset_ns(pd)
    assert one == pytest.approx(skew + 20 * US)
    below, above = clock2.bracket_ns(pd)
    assert above == pytest.approx(one)
    assert below == pytest.approx(skew - 60 * US)
    assert clock2.offset_ns((below, above)) \
        == pytest.approx(one - 40 * US)          # the middle


def test_bracket_holds_when_the_host_runs_ahead():
    """The first program waits 28 ms for its operand and the others
    queue behind it: every lag is long, the one-sided bound is 28 ms
    off, the beacon's bracket is not."""
    skew = -1.2 * MS
    pd = _profile(skew, lambda i: 28 * MS + i * 10 * US)
    assert hostspans.clock_offset_ns(pd) - skew >= 25 * MS
    below, above = clock2.bracket_ns(pd)
    # above from the beacon's own enqueue, 25 us before its execution,
    # not from its span's start, 30 us before
    assert above == pytest.approx(skew + 25 * US)
    assert below == pytest.approx(skew - 60 * US)
    assert abs(clock2.offset_ns((below, above)) - skew) < 50 * US
    # an enqueue event outside the span is another launch's: the span
    pd.planes[0].lines[0].events[-1].start_ns -= 1 * MS
    assert clock2.bracket_ns(pd)[1] == pytest.approx(skew + 30 * US)


def test_a_beacon_that_waited_widens_the_bracket_around_the_skew():
    skew = -1.2 * MS
    pd = _profile(skew, lambda i: 28 * MS)
    late = pd.planes[1].lines[0].events[-1]      # the beacon's run
    late.start_ns += 70 * US            # 95 us after its enqueue
    pd.planes[0].lines[0].events[-2].duration_ns += 70 * US  # the span
    below, above = clock2.bracket_ns(pd)
    assert above - below == pytest.approx(155 * US)
    assert below <= skew <= above
    assert clock2.offset_ns((below, above)) \
        == pytest.approx((below + above) / 2)


def test_no_beacon_no_bracket():
    pd = _profile(-1.2 * MS, lambda i: 20 * US, beacon=False)
    assert clock2.beacons(pd) == [] and clock2.bracket_ns(pd) is None
    assert clock2.offset_ns(None) is None
    assert uploadtrace.read(pd, "gesv") is None
    # PR 25's recordings hold none either
    old = reduce_trace.load(os.path.join(HERE, "data", "ping.xplane.pb"))
    assert clock2.bracket_ns(old) is None
    # a span with no execution to pair it with is no beacon
    pd = _profile(-1.2 * MS, lambda i: 20 * US)
    pd.planes[1].lines[0].events.pop()
    assert clock2.bracket_ns(pd) is None


def test_upload_slice_by_hand():
    """The slice opens with the first `matrix::h2d`, before the root:
    idle [0,100] and [200,300]; the upload is in flight over [10,90]."""
    sl = uploadtrace.UploadSlice(
        [[(100, 200), (300, 400)]],
        [(20, 400, "gesv"), (0, 10, "matrix::h2d"),
         (10, 90, uploadtrace.READY)], 0.0, "gesv")
    assert sl.idle == [[[0, 100], [200, 300]]]
    assert sl.cover((uploadtrace.READY,)) == pytest.approx(40.0)
    # the device's stamps 50 early: its first operation starts at 150
    late = uploadtrace.UploadSlice(
        [[(100, 200)]], [(20, 250, "gesv"), (0, 10, "matrix::h2d"),
                         (10, 90, uploadtrace.READY)], -50.0, "gesv")
    assert late.idle == [[[0, 150.0]]]


#: what `tools/upload_probe.py --read` reads of each recording as kept
#: (recorded on the chip, PR 36, call C, then cut down by `--strip`;
#: the chip printed shares of 9.39, 5.92 and 20.65 of the whole files,
#: whose idle time is between `XLA Ops`, not between `XLA Modules`):
#: bracket in us, idle seconds, share
RECORDED = {
    "gesv": {"bracket_us": 430.683, "idle_s": 0.019167168,
             "share": 9.26589676680457},
    "gels": {"bracket_us": 535.001, "idle_s": 0.031173251,
             "share": 5.861175659863003},
    "heev": {"bracket_us": 610.08, "idle_s": 0.008092249,
             "share": 19.757177516411076},
}


@pytest.mark.parametrize("routine", sorted(RECORDED))
def test_recorded_solve_reads_as_the_chip_printed_it(routine):
    pd = reduce_trace.load(DATA % routine)
    sl, (below, above) = uploadtrace.read(pd, routine)
    want = RECORDED[routine]
    assert (above - below) / 1e3 == pytest.approx(want["bracket_us"])
    assert sl.idle_ns / 1e9 == pytest.approx(want["idle_s"])
    assert sl.cover((uploadtrace.READY,)) == pytest.approx(want["share"])
    assert below <= sl.offset_ns <= above
    # the beacon's execution inside its span on the corrected clock
    (s, e), = [ev[:2] for ev in hostspans.host_events(
        pd, {clock2.BEACON_SPAN})]
    run, = [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
            for p in pd.planes if p.name.startswith("/device:")
            for ln in p.lines if ln.name == reduce_trace.MODULES
            for ev in ln.events if ev.name.startswith(clock2.BEACON_PROGRAM)]
    assert s <= run[0] - sl.offset_ns + 1 and run[1] - sl.offset_ns <= e + \
        (above - below)
    # one ready span per hand-over, the same `seq`, opened after it
    evs = hostspans.host_events(pd, {"matrix::h2d", uploadtrace.READY})
    by = {}
    for s, e, name, args in evs:
        by.setdefault(args["seq"], {})[name] = (s, e)
    assert by and all(set(v) == {"matrix::h2d", uploadtrace.READY}
                      and v[uploadtrace.READY][0] >= v["matrix::h2d"][1]
                      for v in by.values())


def _run(cell, **kv):
    return {"workload": cell, "trace": None, "counters": {},
            "histograms": {}, "spans": {}, "records": {"solves": 4}, **kv}


@pytest.mark.parametrize("name", sorted(UPLOAD_METRICS))
def test_upload_metric_is_found_and_silent_without_its_source(name):
    cell, _ = UPLOAD_METRICS[name]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [cell] and entry["better"] == "lower"
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == entry["moves"])
    assert cell in moved["workloads"]
    compute = bench_run.load_module("layer_metrics", name).compute
    assert compute(_run(cell)) is None
    # a reduced trace but no xplane of this run to read, or one of a
    # program without the spans (the parent commit's): nothing, no raise
    got = compute(_run(cell, trace={"busy_s": 1.0, "window_s": 2.0}))
    assert got is None or isinstance(got, float)


@pytest.mark.parametrize("name", sorted(UPLOAD_METRICS))
def test_upload_metric_computes(name, monkeypatch):
    cell, routine = UPLOAD_METRICS[name]
    compute = bench_run.load_module("layer_metrics", name).compute
    if routine is not None:         # on the solve recorded on the chip
        monkeypatch.setattr(Tracer, "xplane", lambda self: DATA % routine)
        got = compute(_run(cell, trace={"busy_s": 1.0, "window_s": 2.0}))
        want = RECORDED[routine]
        assert got == pytest.approx(want["bracket_us" if "bracket" in name
                                         else "share"])
        # the parent's trace of the same cell: no beacon, no number
        monkeypatch.setattr(Tracer, "xplane", lambda self: os.path.join(
            HERE, "data", "ping.xplane.pb"))
        assert compute(_run(cell, trace={"busy_s": 1.0,
                                         "window_s": 2.0})) is None
        return
    run = _run(cell, spans={"matrix::h2d_ready": 0.112, "matrix::h2d": 0.005,
                            "ooc::h2d_ready": 2.8, "ooc::h2d": 3.3},
               counters={"ooc.d2h_touched_bytes": 4 * 36 * 4096 * 4096 * 4,
                         "grid.pack_touched_bytes": 0})
    want = {"upload_ready_s": 0.028, "stream.h2d_ready_s": 0.7,
            "stream.writeback_fault_gb": 2.415919104,
            "grid.pack_fault_gb": 0.0}
    key = next(k for k in want if name.endswith(k))
    assert compute(run) == pytest.approx(want[key])
