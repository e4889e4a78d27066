"""What the staging layer says with the obs bus on (PR 36): the span
that stays open until an upload is on the device (`watch_ready`, the
`obs-ready` thread), the clock beacon under a profiler session, the
resident set's growth under the copies; its off state; and a
rehearsal of every benchmark cell publishing the spans the new
metrics read (tests/test_upload_metrics.py has the readers')."""

import gc
import json
import os
import subprocess
import sys
import threading
import time
import weakref

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.obs import events as obs_events
from slate_tpu.obs import metrics as obs_metrics

from benchmarks.lib import hostspans, reduce_trace, uploadtrace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def bus():
    obs.disable()
    obs_events.clear()
    obs_metrics.reset()
    yield
    obs_events.flush_ready(30.0)    # nothing published into the next test
    obs.disable()
    obs_events.clear()
    obs_metrics.reset()


def _ready_threads():
    return [t for t in threading.enumerate() if t.name == "obs-ready"]


def _named(name):
    return [e for e in obs.bus_events() if e.name == name]


# -- the ready span ---------------------------------------------------------

def test_every_constructor_upload_gets_one_ready_span(bus, rng):
    obs.enable()
    a = rng.standard_normal((96, 96)).astype(np.float32)
    mats = [st.Matrix(a, mb=32), st.Matrix(a[:, :8].copy(), mb=32),
            st.HermitianMatrix(st.Uplo.Lower, a + a.T, mb=32),
            st.TriangularMatrix(st.Uplo.Upper, a, mb=32)]
    # a device array is no upload
    st.Matrix(mats[0].data, mb=32)
    assert obs_events.flush_ready(30.0)
    h2d, ready = _named("matrix::h2d"), _named("matrix::h2d_ready")
    assert len(h2d) == len(ready) == len(mats)
    seqs = [e.args["seq"] for e in h2d]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert [e.args["seq"] for e in ready] == seqs
    for h, r in zip(h2d, ready):
        assert r.args["bytes"] == h.args["bytes"] and r.cat == "staging"
        assert r.thread == "obs-ready" and h.thread != "obs-ready"
        assert r.t0 >= h.t1 and r.args["queued_us"] >= 0
        assert "gone" not in r.args
    # one thread, in order: spans of the name never overlap
    assert all(x.t1 <= y.t0 for x, y in zip(ready, ready[1:]))
    assert len(_ready_threads()) == 1


def test_ready_thread_keeps_no_reference(bus):
    import jax.numpy as jnp
    obs.enable()
    x = jnp.arange(1024.0)
    ref = weakref.ref(x)
    obs_events.watch_ready("t::ready", x, bytes=int(x.nbytes))
    assert obs_events.flush_ready(30.0)
    del x
    gc.collect()
    assert ref() is None
    ev, = _named("t::ready")
    assert ev.args["bytes"] == 8192 and ev.cat == "staging"


def test_deleted_array_closes_its_span_gone(bus):
    import jax.numpy as jnp
    obs.enable()
    x, y = jnp.ones((64,)), jnp.ones((64,))
    x.delete()
    obs_events.watch_ready("t::ready", x, k=0)
    obs_events.watch_ready("t::ready", y, k=1)      # the thread lives on
    assert obs_events.flush_ready(30.0)
    first, second = _named("t::ready")
    assert first.args["gone"] == 1 and first.args["k"] == 0
    assert "gone" not in second.args and second.args["k"] == 1


def test_many_threads_hand_over_to_one_ready_thread(bus):
    """Twelve threads hand uploads over at once (the stream engine's
    prefetch worker and its main thread do): every span is published
    once, by one `obs-ready` thread, none overlapping."""
    import sys
    import jax.numpy as jnp
    obs.enable()
    x = jnp.ones((8,))
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def hand(k):
            for i in range(100):
                obs_events.watch_ready("t::ready", x, k=k, i=i)
        ts = [threading.Thread(target=hand, args=(k,)) for k in range(12)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not any(t.is_alive() for t in ts)
        assert obs_events.flush_ready(60.0)
    finally:
        sys.setswitchinterval(old)
    evs = _named("t::ready")
    assert sorted((e.args["k"], e.args["i"]) for e in evs) \
        == [(k, i) for k in range(12) for i in range(100)]
    assert {e.thread for e in evs} == {"obs-ready"} \
        and len({e.tid for e in evs}) == 1 and len(_ready_threads()) == 1
    assert all(a.t1 <= b.t0 for a, b in zip(evs, evs[1:]))
    # each thread's own hand-overs were taken in its order
    for k in range(12):
        mine = [e.args["i"] for e in evs if e.args["k"] == k]
        assert mine == sorted(mine)


def test_ready_span_is_in_the_host_plane_on_its_own_line(bus, rng,
                                                         host_plane):
    obs.enable()
    a = rng.standard_normal((64, 64)).astype(np.float32)

    def body():
        st.Matrix(a, mb=32)
        assert obs_events.flush_ready(30.0)

    seen = {e[2]: e for e in host_plane(
        body, ["matrix::h2d", "matrix::h2d_ready"])}
    assert seen["matrix::h2d_ready"][3]["seq"] \
        == seen["matrix::h2d"][3]["seq"]
    assert seen["matrix::h2d_ready"][3]["bytes"] == a.nbytes
    assert seen["matrix::h2d_ready"][0] >= seen["matrix::h2d"][1]


# -- the off state ------------------------------------------------------------

class _Untouchable:
    """Raises on any attribute: `watch_ready` must not look at it."""

    def __getattribute__(self, name):
        raise AssertionError("touched .%s with the bus off" % name)


def test_off_state_makes_no_thread_and_reads_nothing(bus, rng,
                                                     monkeypatch):
    from slate_tpu.linalg.ooc import posv_ooc
    calls = []
    real = obs_events._resident_bytes
    monkeypatch.setattr(obs_events, "_resident_bytes",
                        lambda: calls.append(1) or real())
    monkeypatch.setattr(obs_events, "_profiling",
                        lambda: calls.append(2) or False)
    deadline = time.time() + 10         # an earlier test's thread ends
    while _ready_threads() and time.time() < deadline:
        time.sleep(0.01)
    assert _ready_threads() == []
    assert obs_events.watch_ready("t::ready", _Untouchable()) is None
    assert obs_events.clock_beacon(1 << 30) is None
    assert obs.span("a", resident="a.touched_bytes") \
        is obs_events._NO_SPAN
    a = rng.standard_normal((256, 256)).astype(np.float32)
    spd = a @ a.T + 256 * np.eye(256, dtype=np.float32)
    st.gesv(st.Matrix(a, mb=64), st.Matrix(a[:, :2].copy(), mb=64))
    posv_ooc(spd, a[:, :2].copy(), panel_cols=32,
             cache_budget_bytes=5 * 256 * 32 * 4)
    assert _ready_threads() == [] and calls == []
    assert obs.bus_events() == []
    assert obs.snapshot()["metrics"]["counters"] == {}
    # on, the same sites do both; off again, the thread ends
    obs.enable()
    st.Matrix(a, mb=64)
    with obs.span("a", resident="a.touched_bytes"):
        pass
    assert len(_ready_threads()) == 1 and calls == [1, 1]
    obs.disable()
    deadline = time.time() + 10
    while _ready_threads() and time.time() < deadline:
        time.sleep(0.01)
    assert _ready_threads() == []


# -- the clock beacon ---------------------------------------------------------

@pytest.fixture
def profiler(tmp_path):
    """A profiler session with the benchmark's options, as the traced
    solve of `run.py --trace 1` runs under."""
    from benchmarks.lib.tracer import Tracer
    tr = Tracer(str(tmp_path / "trace"))
    tr.start()
    yield tr
    if tr.active:
        tr.stop()


BIG = np.ones((2048, 2048), np.float32)             # 16 MiB exactly


def test_beacon_only_on_a_large_upload_and_never_compiles(bus, profiler):
    from benchmarks.lib.compiles import Compiles
    assert BIG.nbytes == obs_events.BEACON_MIN_BYTES
    st.Matrix(BIG, mb=256)          # bus off: the constructor's own programs
    comp = Compiles()
    obs.enable()                    # compiles the beacon, once a process
    s0 = comp.snap()
    st.Matrix(BIG, mb=256)
    assert obs_events.flush_ready(60.0)
    assert comp.since(s0)["programs"] == 0          # none at the launch
    sync, = _named("obs::clock_sync")
    h2d, = _named("matrix::h2d")
    ready, = _named("matrix::h2d_ready")
    assert sync.cat == "trace" and sync.thread == h2d.thread != "obs-ready"
    # launched and waited for on the constructing thread before the
    # hand-over: ahead of any program that waits for the upload
    assert sync.t1 <= h2d.t0 and h2d.t1 <= ready.t0
    st.Matrix(BIG[:, :2047].copy(), mb=256)         # one column short
    # watching alone launches none, whatever the size
    import jax.numpy as jnp
    obs_events.watch_ready("t::ready", jnp.asarray(BIG), bytes=BIG.nbytes)
    assert obs_events.flush_ready(60.0)
    assert len(_named("obs::clock_sync")) == 1
    assert len(_named("matrix::h2d_ready")) == 2
    # the session over, the same upload launches none
    profiler.stop()
    st.Matrix(BIG, mb=256)
    assert len(_named("obs::clock_sync")) == 1


def test_bus_on_without_a_profiler_launches_no_program(bus, monkeypatch):
    """A bus that is merely on adds no launch and no wait to a
    constructor: the beacon is for an xplane to be read in."""
    launched = []
    monkeypatch.setattr(obs_events, "_clock_sync",
                        lambda: launched.append(1))
    obs.enable()
    assert not obs_events._profiling()
    st.Matrix(BIG, mb=256)
    obs_events.clock_beacon(1 << 30)
    assert obs_events.flush_ready(60.0)
    assert launched == [] and _named("obs::clock_sync") == []
    assert len(_named("matrix::h2d_ready")) == 1
    monkeypatch.setattr(obs_events, "_profiling", lambda: True)
    obs_events.clock_beacon(1 << 30)
    assert launched == [1]


def test_enable_without_the_beacon_compiles_and_launches_none(
        bus, profiler, monkeypatch):
    from benchmarks.lib.compiles import Compiles
    monkeypatch.setattr(obs_events, "_beacon", None)
    st.Matrix(BIG, mb=256)          # bus off: the constructor's own programs
    comp = Compiles()
    s0 = comp.snap()
    obs.enable(beacon=False)
    assert obs_events._beacon is None
    st.Matrix(BIG, mb=256)
    assert obs_events.flush_ready(60.0)
    assert comp.since(s0)["programs"] == 0
    assert _named("obs::clock_sync") == []
    assert len(_named("matrix::h2d_ready")) == 1


def test_beacon_program_is_named_for_the_device_trace(bus):
    import jax
    obs.enable()
    run, x = obs_events._beacon
    assert "obs_clock_sync" in run.as_text()
    assert x.shape == (1,) and x.devices() == {jax.devices()[0]}


# -- the resident set's growth under a copy -----------------------------------

needs_statm = pytest.mark.skipif(
    obs_events._resident_bytes() is None,
    reason="no /proc/self/statm on this platform")


@needs_statm
def test_resident_counts_a_fresh_buffer_not_a_touched_one(bus):
    obs.enable()
    src = np.ones(64 << 20, np.uint8)
    with obs.span("t::copy", cat="staging",
                  resident="t.copy_touched_bytes") as first:
        buf = np.empty_like(src)
        np.copyto(buf, src)
    with obs.span("t::copy", cat="staging",
                  resident="t.copy_touched_bytes") as again:
        np.copyto(buf, src)
    # sized in bytes, whatever the page a fault maps
    assert first.args["touched_bytes"] >= 0.9 * src.nbytes
    assert first.args["touched_bytes"] >= 10 * again.args["touched_bytes"]
    assert obs.snapshot()["metrics"]["counters"]["t.copy_touched_bytes"] \
        == first.args["touched_bytes"] + again.args["touched_bytes"]
    assert [e.args["touched_bytes"] for e in _named("t::copy")] \
        == [first.args["touched_bytes"], again.args["touched_bytes"]]
    # the count is the whole process's: another thread's first touch
    # under the span is in it, which is why the sites are the spans
    # AROUND the copying threads; and it is the set's growth, so what
    # was mapped and freed again under the span is not
    kept = []

    def touch(keep):
        fresh = np.empty_like(src)
        np.copyto(fresh, src)
        if keep:
            kept.append(fresh)

    for keep in (True, False):
        t = threading.Thread(target=touch, args=(keep,))
        with obs.span("t::around", resident="t.around_touched_bytes") as sp:
            t.start()
            t.join()
        if keep:
            assert sp.args["touched_bytes"] >= 0.9 * src.nbytes
        else:
            assert sp.args["touched_bytes"] <= 0.1 * src.nbytes
    # a set that shrank counts nothing
    with obs.span("t::free", resident="t.free_touched_bytes") as sp:
        del kept[:], buf
    assert sp.args["touched_bytes"] == 0


def test_resident_does_nothing_without_statm(bus, monkeypatch):
    monkeypatch.setattr(obs_events, "_STATM", "/nonexistent/statm")
    assert obs_events._resident_bytes() is None
    obs.enable()
    with obs.span("t::copy", resident="t.copy_touched_bytes") as sp:
        pass
    assert "touched_bytes" not in sp.args
    assert "t.copy_touched_bytes" \
        not in obs.snapshot()["metrics"]["counters"]


@needs_statm
def test_streamed_solve_publishes_ready_spans_and_writer_touches(bus, rng):
    """posv_ooc in the streamed cell's shape in small (8 panels, 5
    resident; tall enough for the writer's chunk threads, and a factor
    of 64 MiB, which the allocator maps fresh whatever ran before)."""
    from slate_tpu.linalg.ooc import posv_ooc
    n, w = 4096, 512
    g = rng.standard_normal((n, n)).astype(np.float32)
    a = g @ g.T / 64 + np.eye(n, dtype=np.float32)
    b = rng.standard_normal((n, 8)).astype(np.float32)
    obs.enable()
    posv_ooc(a, b, panel_cols=w, cache_budget_bytes=5 * n * w * 4)
    assert obs_events.flush_ready(60.0)
    h2d, ready = _named("ooc::h2d"), _named("ooc::h2d_ready")
    assert len(h2d) == len(ready) > 8
    assert sorted(e.args["bytes"] for e in h2d) \
        == sorted(e.args["bytes"] for e in ready)
    assert all(e.thread == "obs-ready" for e in ready)
    assert _named("obs::clock_sync") == []          # no beacon here
    c = obs.snapshot()["metrics"]["counters"]
    d2h = _named("ooc::d2h")
    assert d2h and _named("ooc::d2h_chunk")
    assert c["ooc.d2h_touched_bytes"] \
        == sum(e.args["touched_bytes"] for e in d2h)
    # the factor's lower trapezoid, 36 of 64 blocks of 512 x 512 f32,
    # is first written by the writer
    assert c["ooc.d2h_touched_bytes"] >= 0.5 * 36 * w * w * 4
    assert all("touched_bytes" not in (e.args or {})
               for name in ("ooc::d2h_chunk", "ooc::h2d_pack")
               for e in _named(name))


@needs_statm
def test_grid_placement_counts_what_it_touches(bus, rng):
    import jax
    from slate_tpu.parallel.sharding import place
    grid = st.make_grid(2, 2, devices=jax.devices()[:4])
    obs.enable()
    a = rng.standard_normal((4096, 4096)).astype(np.float32)
    kept = place(a, grid)
    placed, = _named("matrix::h2d")
    assert len(_named("grid::pack")) == 4
    assert all("touched_bytes" not in e.args
               for e in _named("grid::pack") + _named("grid::place"))
    assert obs.snapshot()["metrics"]["counters"]["grid.pack_touched_bytes"] \
        == placed.args["touched_bytes"]
    # on the CPU the devices' own copies are host memory too: the
    # placement maps at least the matrix once more
    assert placed.args["touched_bytes"] >= 0.5 * a.nbytes
    del kept


# -- a rehearsal of each cell publishes the spans of its new metrics ----------

#: as tests/test_hostspans.py's: the in-core rehearsal grown to the carry
#: form's four panels, and the streamed one to the 2048 rows from which
#: the writer copies a panel out in chunks, for this run only
GROW = {"incore-gesv": {"n": 2048, "mb": 256},
        "stream-posv": {"n": 2048, "panel_cols": 256}}

#: run.py as it is, but for the size above, the trace's directory (one
#: per checkout otherwise) and the beacon's threshold: a rehearsal's
#: uploads are a megabyte or less
_RUN = """
import json, sys
sys.path.insert(0, %(root)r)
from benchmarks import run
from benchmarks.lib.tracer import Tracer
from slate_tpu.obs import events
events.BEACON_MIN_BYTES = 1 << 12
resolve, grow, init = run.resolve, json.loads(%(grow)r), Tracer.__init__
def grown(*a):
    cell, cfg, mix = resolve(*a)
    return cell, {**cfg, **grow}, mix
run.resolve = grown
Tracer.__init__ = lambda self, directory: init(self, %(trace)r)
sys.exit(run.main(sys.argv[1:]))
"""

#: metrics of the bus's sums and counters, which a rehearsal's line
#: carries (the shares and brackets need a device plane): seconds an
#: upload was in flight, and the resident set's growth, which a toy
#: size may leave at 0.0
ON_THE_CPU = {
    "incore-gesv": ["solve.upload_ready_s"],
    "tall-gels": ["lstsq.upload_ready_s"],
    "incore-heev": ["heev.upload_ready_s"],
    "stream-posv": ["stream.h2d_ready_s", "stream.writeback_fault_gb"],
    "grid-posv": ["grid.pack_fault_gb"],
}


@pytest.mark.parametrize("cell", sorted(uploadtrace.SPANS))
def test_rehearsal_publishes_the_upload_spans(cell, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if cell == "grid-posv":
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    out = subprocess.run(
        [sys.executable, "-c",
         _RUN % {"root": ROOT, "grow": json.dumps(GROW.get(cell, {})),
                 "trace": str(tmp_path / "trace")},
         "--workload", cell, "--seed", "3600000019", "--seconds", "1.5",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    window = next(ln for ln in lines if ln.get("phase") == "window")
    assert window["compiles_in_window"]["programs"] == 0
    for name in ON_THE_CPU[cell]:
        assert name in last["metrics"], name
        if name.endswith("_ready_s"):
            assert last["metrics"][name]["value"] > 0, name
    xplane = next(ln["xplane"] for ln in lines if ln.get("phase") == "trace")
    want = set(uploadtrace.SPANS[cell])
    seen = hostspans.host_events(reduce_trace.load(xplane), want)
    assert want <= {e[2] for e in seen}, sorted(want - {e[2] for e in seen})
    for s, e, name, args in seen:
        if name == uploadtrace.TOUCHED.get(cell):
            assert isinstance(args["touched_bytes"], int)
        if name.endswith("_ready"):
            assert "queued_us" in args and "gone" not in args
