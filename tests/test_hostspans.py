"""The program's spans on the profiler's clock (PR 25): the bridge from
the obs bus to `jax.profiler.TraceAnnotation`, its off state, the span
sites against the accumulators they sit beside, and a rehearsal of
every benchmark cell publishing the spans its metrics read."""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.obs import events as obs_events
from slate_tpu.obs import metrics as obs_metrics

# the yardstick's own tests of the reader and of the metric files
# (benchmarks/tests is outside tier-1): counted here too
from benchmarks.tests.test_span_metrics import *  # noqa: F401,F403,E402
from benchmarks.lib import hostspans, reduce_trace          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def bus():
    obs.disable()
    obs_events.clear()
    obs_metrics.reset()
    yield
    obs.disable()
    obs_events.clear()
    obs_metrics.reset()


def _spd(rng, n):
    a = rng.standard_normal((n, n)).astype(np.float32)
    return a @ a.T + n * np.eye(n, dtype=np.float32)


# -- (a) the bridge -------------------------------------------------------

def test_spans_reach_the_host_plane_with_their_arguments(bus, host_plane):
    obs.enable()

    def worker():
        with obs.span("ooc::h2d", cat="staging", bytes=4096):
            pass

    def body():
        with obs.driver("gesv", shape=(8, 8), dtype="float32"):
            obs_events.note(factor="tiled", nb=512)
            with obs.span("getrf::panel", cat="step", k=3):
                t = threading.Thread(target=worker, name="ooc-h2d_0")
                t.start()
                t.join()

    seen = {e[2]: e for e in host_plane(
        body, ["gesv", "getrf::panel", "ooc::h2d"])}
    assert seen["ooc::h2d"][3] == {"bytes": 4096}
    assert seen["getrf::panel"][3] == {"k": 3}
    assert seen["gesv"][3] == {"shape": "8x8", "dtype": "float32",
                               "factor": "tiled", "nb": 512}
    # nested as opened, on the one clock
    assert seen["gesv"][0] <= seen["getrf::panel"][0] \
        <= seen["ooc::h2d"][0] < seen["ooc::h2d"][1] \
        <= seen["getrf::panel"][1] <= seen["gesv"][1]
    # and the bus holds the same three, the route on the driver's record
    bus_evs = {e.name: e for e in obs.bus_events()}
    assert set(bus_evs) == {"gesv", "getrf::panel", "ooc::h2d"}
    assert bus_evs["gesv"].args["factor"] == "tiled"
    assert bus_evs["gesv"].cat == "driver"


def test_getrf_span_carries_the_route(bus, rng):
    obs.enable()
    n = 2048
    a = rng.standard_normal((n, n)).astype(np.float32)
    st.getrf(st.Matrix(a, mb=256))
    ev = [e for e in obs.bus_events(cat="driver") if e.name == "getrf"]
    assert len(ev) == 1
    assert {k: ev[0].args[k] for k in ("lu", "factor", "form", "nb",
                                       "panel")} == \
        {"lu": "PPLU", "factor": "tiled", "form": "carry", "nb": 512,
         "panel": "native"}
    steps = [e.name for e in obs.bus_events(cat="step")]
    assert steps.count("getrf::panel") == 4 \
        and steps.count("getrf::pivots") == 4 \
        and steps.count("getrf::update") == 3 \
        and steps.count("getrf::reorder") == 1
    assert [e.args["bytes"] for e in obs.bus_events(cat="staging")
            if e.name == "matrix::h2d"] == [a.nbytes]


# -- (b) the off state ----------------------------------------------------

def test_off_state_constructs_nothing(bus, rng, monkeypatch):
    """With the bus off no new site constructs a TraceAnnotation, takes
    a span object or publishes: `span()` hands back the one shared
    no-op."""
    import jax
    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(obs_events, "_annotation", Counting)
    assert obs.span("a", cat="x", k=1) is obs.span("b")
    with obs.span("a") as sp:
        assert sp is obs_events._NO_SPAN
    obs_events.note(form="carry")                   # no driver span: no-op
    # the drivers: matrix::h2d, getrf::*, getrs (carry form and the
    # unrolled one-panel form)
    from slate_tpu.linalg import lu
    from slate_tpu.linalg.ooc import posv_ooc
    a = rng.standard_normal((256, 256)).astype(np.float32)
    lu._getrf_carry(st.Matrix(a, mb=64).data, 64)
    st.gesv(st.Matrix(a, mb=64), st.Matrix(a[:, :2].copy(), mb=64))
    # the stream engine: ooc::h2d_pack/put, wait_stage, wait_write
    spd = _spd(rng, 256)
    posv_ooc(spd, a[:, :2].copy(), panel_cols=64,
             cache_budget_bytes=3 * 256 * 64 * 4)
    # serving: serve::submit, batch::flush and its children, the
    # queue-wait histogram
    from slate_tpu import batch, serve
    with serve.Server(queue=batch.CoalescingQueue(background=False)) \
            as srv:
        srv.submit("posv", spd[:64, :64] + 64 * np.eye(64, dtype=np.float32),
                   a[:64, 0].copy()).result(timeout=120)
    assert made == []
    assert obs.bus_events() == []
    assert obs.snapshot()["metrics"]["histograms"] == {}
    # switched on, the same constructor is the one the sites use
    obs.enable()
    with obs.span("a"):
        pass
    assert [m[0] for m in made] == ["a"]


# -- (f) the spans agree with the accumulators beside them -----------------

def test_wait_stage_spans_equal_the_engines_accumulators(bus, rng):
    from slate_tpu.linalg import stream
    from slate_tpu.linalg.ooc import potrf_ooc
    obs.enable()
    n, w = 512, 64
    potrf_ooc(_spd(rng, n), panel_cols=w,
              cache_budget_bytes=3 * n * w * 4)
    stats = stream.last_stats()
    spans = [e for e in obs.bus_events(cat="staging")
             if e.name == "ooc::wait_stage"]
    kinds = {e.args["kind"] for e in spans}
    assert kinds == {"prefetch", "sync"}
    want = stats["prefetch_wait_seconds"] + stats["sync_upload_seconds"]
    got = sum(e.dur for e in spans)
    # the span is opened just outside the engine's own two clock
    # readings: they differ by what entering and leaving one costs
    assert abs(got - want) <= 2e-4 * len(spans) + 2e-6
    assert got > 0
    # what a wait contains is beside it on the bus
    names = {e.name for e in obs.bus_events(cat="staging")}
    assert {"ooc::h2d", "ooc::h2d_pack", "ooc::h2d_put", "ooc::prefetch",
            "ooc::wait_write", "ooc::d2h", "ooc::writeback"} <= names
    pack = sum(e.dur for e in obs.bus_events(cat="staging")
               if e.name == "ooc::h2d_pack")
    h2d = sum(e.dur for e in obs.bus_events(cat="staging")
              if e.name == "ooc::h2d")
    assert 0 < pack < h2d


def test_queue_wait_is_observed_once_a_request(bus, rng):
    from slate_tpu import batch
    obs.enable()
    with batch.CoalescingQueue(background=False, max_batch=4) as q:
        ts = [q.submit("posv", _spd(rng, n), np.ones(n, np.float32))
              for n in (24, 40, 24, 100, 24, 24, 24)]     # one inline flush
        q.flush()
        for t in ts:
            t.result(timeout=120)
    snap = obs.snapshot()["metrics"]
    waits = snap["histograms"]["batch.queue_wait_seconds"]
    assert waits["count"] == snap["counters"]["batch.requests"] == 7
    assert waits["min"] >= 0
    evs = obs.bus_events(cat="batch")
    flushes = [e for e in evs if e.name == "batch::flush"]
    assert len(flushes) == snap["counters"]["batch.dispatches"]
    assert sum(e.args["occupancy"] for e in flushes) == 7
    for child in ("batch::stack", "batch::dispatch", "batch::fetch",
                  "batch::resolve"):
        assert sum(e.name == child for e in evs) == len(flushes)
    inline = [e for e in evs if e.name == "batch::inline_flush"]
    assert len(inline) == 1 and inline[0].args["op"] == "posv"


# -- (e) a rehearsal of each cell publishes its spans ----------------------

#: the in-core rehearsal's n=512 is one panel wide (nb 512), which never
#: reaches the carry form the cell's n=8192 takes: the test raises it to
#: four panels, for this run only
GROW = {"incore-gesv": {"n": 2048, "mb": 256}}

#: run.py as it is, but for the size above and for the trace's
#: directory: its fixed `.bench_trace` is one per checkout, and two
#: rehearsals at once (xdist without --dist loadfile) would share it
_RUN = """
import json, sys
sys.path.insert(0, %(root)r)
from benchmarks import run
from benchmarks.lib.tracer import Tracer
resolve, grow, init = run.resolve, json.loads(%(grow)r), Tracer.__init__
def grown(*a):
    cell, cfg, mix = resolve(*a)
    return cell, {**cfg, **grow}, mix
run.resolve = grown
Tracer.__init__ = lambda self, directory: init(self, %(trace)r)
sys.exit(run.main(sys.argv[1:]))
"""


@pytest.mark.parametrize("cell", [hostspans.INCORE, hostspans.STREAM,
                                  hostspans.SERVE])
def test_rehearsal_publishes_the_cells_spans(cell, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         _RUN % {"root": ROOT, "grow": json.dumps(GROW.get(cell, {})),
                 "trace": str(tmp_path / "trace")},
         "--workload", cell, "--seed", "3000000019", "--seconds", "1.5",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.strip().splitlines()]
    assert lines[-1]["correct"] is True
    xplane = next(ln["xplane"] for ln in lines if ln.get("phase") == "trace")
    seen = {e[2] for e in hostspans.host_events(reduce_trace.load(xplane))}
    want = {name for name, cells in hostspans.SPANS.items()
            if cell in cells}
    assert want and want <= seen, sorted(want - seen)
