"""Tests of lib/hostspans.py and of the per-layer metrics read through
it (PR 25), on the CPU:

    python -m pytest benchmarks/tests -q

tests/test_hostspans.py imports them into tier-1 too. The cover shares
on planes made by hand, the device's gaps against the trace reduction's
on the xplane recorded on the chip, the clock offset on a ping recorded
there, and every new metric found by
name, moving an end-to-end metric of its cell, and silent on a run
without a trace.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run                     # noqa: E402
from benchmarks.lib import hostspans, reduce_trace          # noqa: E402

BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))

#: the per-layer metrics PR 25 added, by cell
SPAN_METRICS = {
    "incore-gesv": ["solve.upload_s", "solve.idle_pivot_share",
                    "solve.idle_unattributed_share"],
    "stream-posv": ["stream.idle_stage_wait_share", "stream.idle_h2d_share",
                    "stream.idle_writeback_share", "stream.idle_alloc_share",
                    "stream.idle_unattributed_share", "stream.h2d_pack_s"],
    "serve-steady": ["serve.queue_wait_mean_ms",
                     "serve.stack_ms_per_dispatch",
                     "serve.fetch_ms_per_dispatch", "serve.submit_max_ms",
                     "serve.idle_host_share", "serve.idle_empty_share"],
}


def _slice():
    """Device busy [0,100] [200,300] [500,600]: idle [100,200] and
    [300,500], 300 ns. The main thread waits for a panel over [90,180],
    a worker uploads over [150,320]; the root driver span is open
    throughout, and nothing else is over [320,500]."""
    return hostspans.Slice(
        [[(0, 100), (200, 300), (500, 600)]],
        [(0, 600, "posv_ooc"), (90, 180, "ooc::wait_stage"),
         (150, 320, "ooc::h2d"), (10, 20, "ooc::h2d_pack")])


def test_cover_shares_by_hand():
    sl = _slice()
    assert sl.idle == [[[100, 200], [300, 500]]] and sl.idle_ns == 300
    wait = sl.cover(["ooc::wait_stage"])
    h2d = sl.cover(["ooc::h2d"])
    assert wait == pytest.approx(100 * 80 / 300)
    assert h2d == pytest.approx(100 * 70 / 300)
    # the two threads overlap over [150,180]: the shares sum past their
    # union, which is [100,200] + [300,320]
    both = sl.cover(["ooc::wait_stage", "ooc::h2d"])
    assert both == pytest.approx(100 * 120 / 300) and wait + h2d > both
    # a span that was open only while the device was busy covers nothing
    assert sl.cover(["ooc::h2d_pack"]) == 0.0
    assert sl.cover(["no::such"]) == 0.0


def test_root_only_gap_is_uncovered():
    sl = _slice()
    # [320,500] has the root span open and nothing else: uncovered
    assert sl.cover(["posv_ooc"]) == pytest.approx(100.0)
    assert sl.uncovered() == pytest.approx(100 * 180 / 300)
    # with no span but the root, all of the idle time is uncovered
    bare = hostspans.Slice([[(0, 100), (200, 300)]],
                           [(0, 300, "gesv"), (0, 300, "getrf")])
    assert bare.uncovered() == pytest.approx(100.0)
    # no gap, no share
    assert hostspans.Slice([[(0, 100)]], [(0, 9, "getrs")]).cover(
        ["getrs"]) is None


def test_lead_and_tail_of_a_root_span_are_idle():
    """The device's first operation starts 100 after the solve opened
    and its last ends 100 before the solve returned: both are idle
    time, and the allocation at the solve's head covers part of it."""
    sl = hostspans.Slice([[(100, 200), (300, 400)]],
                         [(0, 500, "posv_ooc"), (0, 80, "ooc::alloc")])
    assert sl.idle == [[[0, 100], [200, 300], [400, 500]]]
    assert sl.cover(["ooc::alloc"]) == pytest.approx(100 * 80 / 300)
    assert sl.uncovered() == pytest.approx(100 * 220 / 300)
    # without a root span the slice has no edges of its own
    assert hostspans.Slice([[(100, 200), (300, 400)]],
                           [(0, 80, "ooc::alloc")]).idle == [[[200, 300]]]
    # an operation that outlasts the root span leaves no tail
    assert hostspans.Slice([[(100, 600)]], [(0, 500, "gesv")]).idle \
        == [[[0, 100]]]


def test_overlap_and_durations_by_hand():
    assert hostspans.overlap_ns([[0, 10], [20, 30]],
                                [[5, 25], [28, 40]]) == 5 + 5 + 2
    assert hostspans.overlap_ns([], [[0, 1]]) == 0
    sl = hostspans.Slice([[(0, 1), (2, 3)]],
                         [(0, 4e6, "batch::stack"), (5, 5 + 2e6, "batch::stack")])
    assert sl.durations("batch::stack") == [4e6, 2e6]
    assert set(hostspans.ROOTS) < set(hostspans.SPANS)


def test_gaps_agree_with_the_trace_reduction():
    """On the xplane recorded on the chip (no span of the program in
    it: PR 24 recorded it) the idle pieces sum to the reduction's gaps."""
    path = os.path.join(HERE, "data", "small.xplane.pb")
    pd = reduce_trace.load(path)
    sl = hostspans.Slice(hostspans.device_ops(pd), hostspans.host_events(pd))
    red = reduce_trace.reduce_file(path, window_s=0.2)
    assert sl.idle_ns / 1e9 == pytest.approx(
        sum(g[1] for g in red["idle_gaps"]))
    assert sl.spans == {} and sl.uncovered() == pytest.approx(100.0)


def test_offset_moves_the_device_onto_the_hosts_clock():
    planes, spans = [[(0, 100), (200, 300)]], [(150, 250, "getrs")]
    assert hostspans.Slice(planes, spans).cover(["getrs"]) \
        == pytest.approx(50.0)
    # the device's stamps are 50 early: its gap is [150,250] to the host
    late = hostspans.Slice(planes, spans, offset_ns=-50.0)
    assert late.idle == [[[150.0, 250.0]]]
    assert late.cover(["getrs"]) == pytest.approx(100.0)


def test_floor_skips_a_lone_outlier():
    assert hostspans.floor_ns([]) == 0.0
    assert hostspans.floor_ns([7.0, 5.0]) == 5.0
    vals = [-9166e3, -1401e3, -1388e3, -1373e3, 35e6, -1343e3]
    assert hostspans.floor_ns(vals) == -1401e3
    assert hostspans.floor_ns(vals[1:]) == -1401e3
    # two pairs that agree with no third are not a floor either
    assert hostspans.floor_ns([-9166e3, -9160e3] + vals[1:]) == -1401e3


def test_clock_offset_on_the_recorded_ping():
    """tools/clock_check.py --ping 200 on the chip (PR 25, when its
    annotation was called clock::launch): the device plane's stamps are
    1.2 ms behind the host plane's, so that uncorrected every
    operation starts before its own launch began."""
    check = bench_run.load_module("tools", "clock_check")
    pd = reduce_trace.load(os.path.join(HERE, "data", "ping.xplane.pb"))
    off = hostspans.clock_offset_ns(pd)
    assert off == pytest.approx(-1234603.0, abs=1.0)
    assert check.done_side_ns(pd) == pytest.approx(-1522643.0, abs=1.0)
    ping = ["clock::launch"]
    assert check.margins(pd, ping, ping, 0.0) == ([], [])
    lead, lag = check.margins(pd, ping, ping, off)
    assert len(lead) == len(lag) == 200
    assert 0 < min(lead) < 200 and 0 < min(lag) < 1000      # microseconds
    # PR 24's recording, another session: another offset of that size
    small = reduce_trace.load(os.path.join(HERE, "data", "small.xplane.pb"))
    assert hostspans.clock_offset_ns(small) == pytest.approx(-1185214.0,
                                                             abs=1.0)


@pytest.mark.parametrize("cell,name", [(c, n) for c, ns in
                                       SPAN_METRICS.items() for n in ns])
def test_span_metric_is_found_and_silent_without_a_trace(cell, name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [cell]
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == entry["moves"])
    assert cell in moved["workloads"]
    compute = bench_run.load_module("layer_metrics", name).compute
    run = {"workload": cell, "trace": None, "counters": {},
           "histograms": {}, "spans": {}, "records": {"solves": 3}}
    assert compute(run) is None
    # a reduced trace but no xplane of this run to read, or one without
    # the program's spans (the parent commit's): nothing, and no raise
    run["trace"] = {"busy_s": 1.0, "window_s": 2.0}
    got = compute(run)
    assert got is None or isinstance(got, float)
