"""obs/ subsystem tests (ISSUE 3): the unified event bus + zero-cost
disabled path, Perfetto JSON round trip, compiled-HLO collective
counts against the dist/ tree schedule, the recompile detector, the
trace satellites (awkward names, cross-thread merge: in the bus and in
the profiler's xplane), and the tune-stats snapshot aliasing fix."""

import dataclasses
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import TiledMatrix, obs
from slate_tpu.core.methods import MethodEig, MethodFactor
from slate_tpu.core.options import Option
from slate_tpu.obs import events as obs_events
from slate_tpu.obs import metrics as obs_metrics
from slate_tpu.obs import xprof
from slate_tpu.utils import trace


@pytest.fixture
def obs_clean():
    """Fresh, disabled observability state around each test."""
    obs.disable()
    obs_events.clear()
    obs_metrics.reset()
    xprof.clear_analyses()
    yield
    obs.disable()
    obs_events.clear()
    obs_metrics.reset()
    xprof.clear_analyses()


def _spd(rng, n):
    a = rng.standard_normal((n, n))
    return a @ a.T + n * np.eye(n)


def dist_opts(grid):
    return {Option.Grid: grid, Option.MethodFactor: MethodFactor.Tiled}


def shard(grid, A):
    return dataclasses.replace(
        A, data=jax.device_put(A.data, grid.matrix_sharding()))


# -- bus ------------------------------------------------------------------

def test_disabled_path_records_nothing(rng, obs_clean):
    """The zero-cost contract: with observability off, a fully
    instrumented driver leaves no events and no counters."""
    A = st.HermitianMatrix(st.Uplo.Lower, _spd(rng, 16), mb=8)
    st.potrf(A)
    with trace.block("not-recorded"):
        pass
    trace.mark("also-not-recorded")
    assert obs.bus_events() == []
    snap = obs.snapshot()
    assert snap["metrics"]["counters"] == {}
    assert snap["drivers"] == {}


def test_bus_merges_sources_and_threads(rng, obs_clean, host_plane):
    """trace blocks, tuner-style marks, driver spans and off-thread
    events all land in ONE stream (the satellite-2 fix: the old
    thread-local buffer dropped worker-thread events), and the spans
    among them in the profiler's xplane, one line per thread."""
    obs.enable()
    A = st.HermitianMatrix(st.Uplo.Lower, _spd(rng, 16), mb=8)

    def worker():
        with trace.block("ooc::off-thread"):
            pass

    def body():
        st.potrf(A)                                  # driver span
        with trace.block("host::stage"):             # trace block
            pass
        trace.mark("tune::fake=1 [frozen]")          # tuner mark
        t = threading.Thread(target=worker, name="stager")
        t.start()
        t.join()

    spans = {"potrf", "host::stage", "ooc::off-thread"}
    seen = host_plane(body, spans)
    evs = obs.bus_events()
    names = {e.name for e in evs}
    assert spans | {"tune::fake=1 [frozen]"} <= names
    # main + worker; the constructor's upload is watched on a third,
    # the bus's own (PR 36)
    tids = {e.tid for e in evs if e.thread != "obs-ready"}
    assert len(tids) == 2
    assert {e.name for e in evs if e.thread == "obs-ready"} \
        <= {"matrix::h2d_ready"}
    # the off-thread block is on the profiler's timeline too
    assert {e[2] for e in seen} == spans
    assert [e for e in evs if e.cat == "driver"]


def test_phases_publish_without_timers_option(rng, obs_clean):
    """trace.phases(opts) publishes phase spans to the bus with no
    Option.Timers plumbing — and still feeds a Timers instance when
    one is passed."""
    obs.enable()
    A = st.HermitianMatrix(st.Uplo.Lower, _spd(rng, 16), mb=8)
    B = TiledMatrix.from_dense(np.ones((16, 2)), 8)
    st.posv(A, B)
    phase_names = {e.name for e in obs.bus_events()
                   if e.cat == "phase"}
    assert {"posv::potrf", "posv::potrs"} <= phase_names
    tm = st.Timers()
    st.posv(A, B, {Option.Timers: tm})
    assert "posv::potrf" in tm.values


# -- Perfetto export ------------------------------------------------------

def test_perfetto_roundtrip(rng, obs_clean, tmp_path):
    """chrome_trace() must round-trip through json with the required
    ph/ts/name keys on every record, span durations in microseconds,
    and thread-name metadata."""
    obs.enable()
    A = st.HermitianMatrix(st.Uplo.Lower, _spd(rng, 16), mb=8)
    st.potrf(A)
    with obs.span("custom", cat="trace", detail=7):
        pass
    obs.counter("queue_depth", 3)
    path = obs.write_trace(str(tmp_path / "run.trace.json"))
    back = json.loads(open(path).read())
    evs = back["traceEvents"]
    assert evs, "no events exported"
    for rec in evs:
        assert {"ph", "ts", "name"} <= set(rec), rec
        assert "pid" in rec and "tid" in rec
    spans = [r for r in evs if r["ph"] == "X"]
    assert spans and all(r["dur"] >= 0 for r in spans)
    assert any(r["ph"] == "C" for r in evs)          # counter sample
    assert any(r["ph"] == "M" for r in evs)          # thread names
    assert any(r.get("args", {}).get("detail") == 7 for r in spans)


# -- recompile detector ---------------------------------------------------

def test_recompile_detector(rng, obs_clean):
    """Fires on a shape change, stays silent on a cache hit (the
    driver body never re-enters Python on a hit, so a second trace at
    a NEW (shape, dtype) key is exactly a recompile)."""
    obs.enable()
    A16 = st.HermitianMatrix(st.Uplo.Lower, _spd(rng, 16), mb=8)
    A24 = st.HermitianMatrix(st.Uplo.Lower, _spd(rng, 24), mb=8)

    def run(A):
        return jax.jit(
            lambda d: st.potrf(dataclasses.replace(A, data=d)).data
        )(jnp.asarray(A.data))

    run(A16)
    assert obs_metrics.recompiles() == 0          # first compile
    run(A16)
    assert obs_metrics.recompiles() == 0          # cache hit: silent
    run(A24)
    assert obs_metrics.recompiles() == 1          # shape change: fires
    assert any(e.name == "recompile:potrf"
               for e in obs.bus_events(cat="jit"))


# -- xprof ----------------------------------------------------------------

def test_xprof_potrf_attribution(rng, obs_clean):
    """analyze(): analytic FLOPs and peak memory from the compiler
    cost model, compile-vs-execute wall split, zero collectives on a
    single device — and obs.report() renders all of it."""
    obs.enable()
    n = 32
    A = st.HermitianMatrix(st.Uplo.Lower, _spd(rng, n), mb=8)

    @jax.jit
    def f(d):
        return st.potrf(dataclasses.replace(A, data=d)).data

    rec = obs.analyze("potrf", f, jnp.asarray(A.data))
    assert rec["flops"] > 0
    assert rec["peak_bytes"] > 0
    assert rec["compile_seconds"] > 0
    assert rec["execute_seconds"] >= 0
    assert rec["collectives"]["total"] == 0
    text = obs.report()
    assert "potrf" in text and "flops" in text
    assert "compile" in text and "execute" in text
    assert "collectives    none" in text


def test_collective_counts_parser():
    hlo = """
  %a = f32[8]{0} collective-permute(%x), source_target_pairs={{0,1}}
  %b = f32[8]{0} all-reduce(%x), to_apply=%sum
  %c = (f32[8], f32[8]) collective-permute-start(%x)
  %d = f32[8]{0} collective-permute-done(%c)
  %e = f32[8]{0} all-gather(%x), dimensions={0}
"""
    counts = obs.collective_counts(hlo)
    # the start/done async pair counts ONCE
    assert counts["collective-permute"] == 2
    assert counts["all-reduce"] == 1
    assert counts["all-gather"] == 1
    assert counts["reduce-scatter"] == 0
    assert counts["total"] == 4


def test_hlo_collectives_match_tree_schedule(rng, grid8, obs_clean):
    """The library form of test_dist.py's ad-hoc HLO assertion: the
    compiled gels_tsqr program contains EXACTLY the ppermutes the
    dist/tree.py schedule issues (schedule_ppermutes), and the driver
    publishes the same number to the comms accounting at trace time."""
    from slate_tpu.dist.tree import schedule_ppermutes
    obs.enable()
    m, n = 96, 8
    a = rng.standard_normal((m, n))
    b = rng.standard_normal((m, 2))
    As = shard(grid8, TiledMatrix.from_dense(a, 8))
    Bs = shard(grid8, TiledMatrix.from_dense(b, 8))

    @jax.jit
    def step(A, B):
        return st.gels_tsqr(A, B, dist_opts(grid8)).data

    expected = schedule_ppermutes(8, 2)          # frozen fanin=2 tree
    assert expected == 3                         # 8 devices, binary
    rec = obs.analyze("gels_tsqr_grid", step, As, Bs, run=False)
    assert rec["collectives"]["collective-permute"] == expected
    assert rec["flops"] > 0 and rec["peak_bytes"] > 0
    # trace-time comms accounting recorded the same schedule
    comms = [e for e in obs.bus_events(cat="comms")
             if e.name == "comms:tsqr_qt"]
    assert comms and comms[-1].args["ppermutes"] == expected
    snap = obs.snapshot()
    assert snap["metrics"]["counters"][
        "comms.ppermute.scheduled"] == expected
    # the acceptance surface: the report shows the matching count
    text = obs.report()
    assert "gels_tsqr_grid" in text
    assert "collective-permute=%d" % expected in text


def test_heev_dc_mesh_report_shows_collectives(rng, grid8, obs_clean):
    """Acceptance: grid heev(DC) analyzed end-to-end shows a nonzero
    collective count in obs.report() (the distributed stedc/back-
    transform resharding), next to FLOPs and peak memory."""
    obs.enable()
    n = 64
    a = rng.standard_normal((n, n))
    a = (a + a.T) / 2
    A1 = st.HermitianMatrix(st.Uplo.Lower, a, mb=8)
    opts = dict(dist_opts(grid8))
    opts[Option.MethodEig] = MethodEig.DC
    As = shard(grid8, A1)

    @jax.jit
    def step(d):
        w, V = st.heev(dataclasses.replace(As, data=d), opts)
        return w, V.data

    rec = obs.analyze("heev_dc_grid", step, As.data)
    assert rec["flops"] > 0 and rec["peak_bytes"] > 0
    assert rec["collectives"]["total"] > 0
    text = obs.report()
    assert "heev_dc_grid" in text
    assert "collectives    " in text and "=" in text.split(
        "collectives    ")[1].split("\n")[0]


# -- metrics wiring -------------------------------------------------------

def test_refine_and_ooc_metrics(rng, obs_clean):
    """Eager gesv_mixed records refine sweep counts; potrf_ooc records
    staging bytes and a driver span (off-thread D2H chunks ride the
    shared bus)."""
    obs.enable()
    n = 32
    a = rng.standard_normal((n, n)) + n * np.eye(n)
    b = rng.standard_normal((n, 2))
    st.gesv_mixed(st.Matrix(a, mb=8), TiledMatrix.from_dense(b, 8))
    snap = obs.snapshot()
    c = snap["metrics"]["counters"]
    assert c.get("refine.ir.calls") == 1
    assert "refine.ir.iters" in snap["metrics"]["histograms"]

    from slate_tpu.linalg.ooc import potrf_ooc
    spd = np.asarray(_spd(rng, 64), np.float64)
    L = potrf_ooc(spd, panel_cols=32)
    np.testing.assert_allclose(np.tril(L) @ np.tril(L).T, spd,
                               atol=1e-8)
    snap = obs.snapshot()
    c = snap["metrics"]["counters"]
    assert c.get("ooc.h2d_bytes", 0) > 0
    assert c.get("ooc.d2h_bytes", 0) > 0
    assert snap["drivers"]["potrf_ooc"]["calls"] == 1


# -- trace satellites -----------------------------------------------------

def test_trace_awkward_names_survive(obs_clean, host_plane):
    """Satellite 1: tuner marks legitimately contain <>& (e.g.
    \"tune::eig.method=<MethodEig.DC: 'dc'> [frozen]\"), and a block
    may: both keep their names letter for letter in the bus, and the
    block in the profiler's xplane."""
    obs.enable()
    mark = "tune::eig.method=<MethodEig.DC: 'dc'> [frozen]"
    block = "a & b <gemm>"

    def body():
        trace.mark(mark)
        with trace.block(block):
            pass

    seen = host_plane(body, [block])
    assert [e.name for e in obs.bus_events(cat="tune")] == [mark]
    assert [e.name for e in obs.bus_events(cat="trace")] == [block]
    assert [e[2] for e in seen] == [block]


def test_tune_stats_snapshot_is_deep_copy():
    """Satellite 3: mutating a snapshot's `recent` entries must not
    reach the live ring."""
    from slate_tpu.tune import stats
    stats.reset()
    stats.record_decision("op", "param", "frozen", 42)
    snap = stats.snapshot()
    snap["recent"][0]["value"] = "CORRUPTED"
    snap2 = stats.snapshot()
    assert snap2["recent"][0]["value"] == repr(42)
    stats.reset()
