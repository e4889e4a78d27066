"""Flight recorder + stall watchdog (ISSUE 14): the off-state
contract (FROZEN ``obs/ledger``/``obs/watchdog`` = "off" ⇒ zero
records, no monitor thread, bitwise-identical OOC driver results),
the per-step phase split's exhaustiveness, the JSONL post-mortem
spill, the watchdog firing on a seeded ``hang`` fault in a sharded
stream, the guard-funnel handoff, the critical-path attribution in
xprof/report, and the Perfetto ledger counter tracks."""

import json
import threading

import numpy as np
import pytest

from slate_tpu import obs
from slate_tpu.dist import shard_ooc
from slate_tpu.linalg import ooc
from slate_tpu.obs import events as obs_events
from slate_tpu.obs import export, health, ledger
from slate_tpu.obs import metrics as obs_metrics
from slate_tpu.obs import xprof
from slate_tpu.resil import faults, guard


@pytest.fixture
def flight_clean():
    """Fresh recorder/watchdog/obs state around each test."""
    def _reset():
        faults.clear()
        ledger.reset()
        health.reset()
        obs.disable()
        obs_events.clear()
        obs_metrics.reset()
        guard.reset_counts()
    _reset()
    yield
    _reset()


def _spd(rng, n):
    x = rng.standard_normal((n, n)).astype(np.float32)
    return x @ x.T / n + 4.0 * np.eye(n, dtype=np.float32)


def _gen(rng, n):
    x = rng.standard_normal((n, n)).astype(np.float32)
    return x + 0.2 * n * np.eye(n, dtype=np.float32)


def _no_watchdog_thread():
    return not any(t.name == "obs-watchdog"
                   for t in threading.enumerate())


# -- off-state contract ---------------------------------------------------

def test_off_state_zero_records_no_thread_bitwise(rng, flight_clean):
    """The acceptance pin: cold FROZEN defaults record NOTHING, start
    no monitor thread, and enabling recorder+watchdog changes no
    driver bit (potrf/geqrf/getrf, partial AND tournament)."""
    n, w = 96, 32
    a, g = _spd(rng, n), _gen(rng, n)
    L0 = ooc.potrf_ooc(a, panel_cols=w)
    qr0 = ooc.geqrf_ooc(g, panel_cols=w)
    lu0 = ooc.getrf_ooc(g, panel_cols=w)
    tp0 = ooc.getrf_tntpiv_ooc(g, panel_cols=w)
    assert ledger.count() == 0
    assert ledger.dropped() == 0
    assert not health.thread_alive()
    assert _no_watchdog_thread()
    assert health.stats()["heartbeats"] == 0

    ledger.enable()
    health.enable()
    L1 = ooc.potrf_ooc(a, panel_cols=w)
    qr1 = ooc.geqrf_ooc(g, panel_cols=w)
    lu1 = ooc.getrf_ooc(g, panel_cols=w)
    tp1 = ooc.getrf_tntpiv_ooc(g, panel_cols=w)
    assert np.array_equal(L0, L1)
    assert np.array_equal(qr0[0], qr1[0])
    assert np.array_equal(qr0[1], qr1[1])
    assert np.array_equal(lu0[0], lu1[0])
    assert np.array_equal(lu0[1], lu1[1])
    assert np.array_equal(tp0[0], tp1[0])
    assert np.array_equal(tp0[1], tp1[1])
    assert ledger.count() > 0
    assert health.thread_alive()
    assert health.stats()["heartbeats"] > 0
    assert health.stats()["stalls"] == 0


def test_off_state_sharded_and_batch(rng, grid8, flight_clean):
    """Sharded stream + batch queue: frozen defaults append nothing;
    enabled, the sharded factor stays bitwise and the dispatch path
    records one ledger entry per flush."""
    from slate_tpu import batch
    n, w = 96, 32
    a = _spd(rng, n)
    b = _spd(rng, 32)
    L0 = shard_ooc.shard_potrf_ooc(a, grid8, panel_cols=w)
    with batch.CoalescingQueue(max_batch=4) as q:
        t = q.submit("potrf", b)
        r0 = t.result()
    assert ledger.count() == 0
    ledger.enable()
    L1 = shard_ooc.shard_potrf_ooc(a, grid8, panel_cols=w)
    assert np.array_equal(L0, L1)
    recs = ledger.records("shard_potrf_ooc")
    nt = (n + w - 1) // w
    assert {r.step for r in recs} == set(range(nt + 1))  # + drain
    with batch.CoalescingQueue(max_batch=4) as q:
        t = q.submit("potrf", b)
        r1 = t.result()
    assert np.array_equal(r0, r1)
    brecs = ledger.records("batch.dispatch")
    assert len(brecs) == 1
    assert brecs[0].meta["op"] == "potrf"
    assert brecs[0].meta["occupancy"] == 1
    assert set(brecs[0].phases) <= {"stage", "factor"}


# -- phase split + spill --------------------------------------------------

def test_phase_split_is_exhaustive(rng, flight_clean):
    ledger.enable()
    n, w = 128, 32
    ooc.potrf_ooc(_spd(rng, n), panel_cols=w)
    recs = ledger.records("potrf_ooc")
    nt = n // w
    assert {r.step for r in recs} == set(range(nt + 1))
    for r in recs:
        assert set(r.phases) <= set(ledger.PHASES)
        assert abs(sum(r.phases.values()) - r.wall) < 1e-6
        assert r.host == 0 and r.owner == 0
    # later steps have visits: the update phase is populated
    assert any(r.phases.get("update", 0) > 0 for r in recs)
    assert any(r.phases.get("factor", 0) > 0 for r in recs)


def test_spill_jsonl_under_ckpt_dir(rng, flight_clean, tmp_path):
    """A recorder with a checkpoint dir leaves the post-mortem JSONL
    next to the durable panels, one flushed line per record."""
    ledger.enable()
    n, w = 96, 32
    ooc.potrf_ooc(_spd(rng, n), panel_cols=w,
                  ckpt_path=str(tmp_path), ckpt_every=2)
    spill = tmp_path / "ledger.host0.jsonl"
    assert spill.exists()
    lines = [json.loads(line) for line in
             spill.read_text().splitlines()]
    assert len(lines) == len(ledger.records("potrf_ooc"))
    assert {rec["step"] for rec in lines} == \
        {r.step for r in ledger.records("potrf_ooc")}
    for rec in lines:
        assert rec["op"] == "potrf_ooc"
        assert set(rec["phases"]) <= set(ledger.PHASES)


def test_ledger_tail_is_incremental(flight_clean):
    ledger.enable()
    ledger.append("batch.dispatch", 0, {"factor": 0.1})
    ledger.append("batch.dispatch", 1, {"factor": 0.2})
    assert [r.step for r in ledger.tail("c1")] == [0, 1]
    assert ledger.tail("c1") == []
    ledger.append("batch.dispatch", 2, {"factor": 0.3})
    assert [r.step for r in ledger.tail("c1")] == [2]
    # an independent consumer keeps its own cursor
    assert [r.step for r in ledger.tail("c2")] == [0, 1, 2]


# -- watchdog -------------------------------------------------------------

def test_watchdog_fires_on_seeded_hang_sharded(rng, grid8,
                                               flight_clean):
    """The acceptance stall test: a seeded kind="hang" fault starves
    the heartbeat mid-sharded-stream; the watchdog publishes
    ``health::stall`` with the stalled op/step/host while the hang is
    still in progress, and the guard's retry then absorbs the
    injected fault so the run still completes correctly."""
    n, w = 96, 32
    a = _spd(rng, n)
    clean = shard_ooc.shard_potrf_ooc(a, grid8, panel_cols=w)
    # after=1: skip panel 2's first-touch staging (during step 0's
    # sweep — the cold prologue the watchdog deliberately ignores)
    # and hang its re-stage during STEP 1's update sweep, when one
    # completed step interval has armed the budget
    faults.install(faults.FaultPlan([
        {"site": "h2d", "match": {"buf": "S", "idx": 2},
         "kind": "hang", "hang_s": 1.2, "after": 1, "times": 1}],
        seed=0))
    obs.enable()
    health.enable(min_budget_s=0.3, interval_s=0.02, stall_factor=4)
    out = shard_ooc.shard_potrf_ooc(a, grid8, panel_cols=w)
    faults.clear()
    assert np.array_equal(clean, out)     # retry absorbed the fault
    stalls = [e for e in obs.bus_events()
              if e.name == "health::stall"]
    assert stalls, "watchdog never fired during the 1.2s hang"
    ev = stalls[0]
    assert ev.cat == "health"
    assert ev.args["op"] == "shard_potrf_ooc"
    assert ev.args["host"] == 0
    assert ev.args["step"] == 1           # the stalled panel step
    assert ev.args["budget_s"] <= 1.0     # fired within budget
    assert health.stats()["stalls"] >= 1
    snap = obs_metrics.snapshot()
    assert snap["counters"]["health.stalls"] >= 1
    # progress resumed after the hang: the stall flag cleared
    assert not health.stats()["ops"]["shard_potrf_ooc"]["stalled"]


def test_watchdog_hands_stall_to_guard_funnel(flight_clean):
    """enable(escalate=True) routes a stall through the resil guard
    funnel: the watchdog_stall rung's counter increments (readable
    with the obs bus off, like every guard count)."""
    import time
    health.enable(min_budget_s=0.1, interval_s=0.02, stall_factor=2,
                  escalate=True)
    # two beats: the cold-start grace never flags an op before one
    # completed step interval
    health.heartbeat("fake_op", 0, total=5)
    health.heartbeat("fake_op", 1, total=5)
    deadline = time.monotonic() + 3.0
    while time.monotonic() < deadline:
        if guard.counts().get("resil.fallback.watchdog_stall"):
            break
        time.sleep(0.02)
    assert guard.counts().get("resil.fallback.watchdog_stall", 0) >= 1
    assert guard.counts().get("resil.fallbacks", 0) >= 1
    assert health.stats()["stalls"] == 1  # one per episode


def test_watchdog_eta_gauge(rng, flight_clean):
    obs.enable()
    ledger.enable()
    health.enable()
    ooc.potrf_ooc(_spd(rng, 128), panel_cols=32)
    gauges = obs_metrics.snapshot()["gauges"]
    assert "health.eta_seconds" in gauges
    assert gauges["health.eta_seconds"] >= 0
    # cold compile on step 0 is not a stall (no-durs grace), and the
    # completion beat retired the track
    assert health.stats()["stalls"] == 0
    assert health.stats()["ops"]["potrf_ooc"]["step"] == 4  # == nt


def test_watchdog_eta_and_stall_on_sharded_lookahead(rng, grid8,
                                                     flight_clean):
    """ISSUE 18 satellite: the watchdog covers the task-graph
    executor's slots as it covers a loop's iterations. At lookahead
    depth 1 (a broadcast in flight while the sweep runs) a seeded
    h2d hang still starves the heartbeat, the ``health::stall``
    instant attributes the stalled op/step, the ETA gauge is
    published, and the run completes bitwise-equal to a clean one."""
    n, w = 128, 32
    a = _spd(rng, n)
    clean = shard_ooc.shard_potrf_ooc(a, grid8, panel_cols=w,
                                      lookahead=1)
    # after=1: panel 3's first touch is step 0's sweep (the cold
    # prologue the watchdog ignores); its re-stage in step 1 hangs
    faults.install(faults.FaultPlan([
        {"site": "h2d", "match": {"buf": "S", "idx": 3},
         "kind": "hang", "hang_s": 1.2, "after": 1, "times": 1}],
        seed=0))
    obs.enable()
    health.enable(min_budget_s=0.3, interval_s=0.02, stall_factor=4)
    out = shard_ooc.shard_potrf_ooc(a, grid8, panel_cols=w,
                                    lookahead=1)
    faults.clear()
    assert np.array_equal(np.asarray(clean), np.asarray(out))
    stalls = [e for e in obs.bus_events()
              if e.name == "health::stall"]
    assert stalls, "watchdog never fired during the 1.2s hang"
    ev = stalls[0]
    assert ev.cat == "health"
    assert ev.args["op"] == "shard_potrf_ooc"
    assert ev.args["step"] >= 1          # past the cold prologue
    assert health.stats()["stalls"] >= 1
    gauges = obs_metrics.snapshot()["gauges"]
    assert "health.eta_seconds" in gauges
    assert gauges["health.eta_seconds"] >= 0
    # progress resumed after the hang: the stall flag cleared
    assert not health.stats()["ops"]["shard_potrf_ooc"]["stalled"]


# -- critical-path attribution + export -----------------------------------

def test_attribution_and_report(rng, flight_clean):
    obs.enable()
    ledger.enable()
    n, w = 128, 32
    ooc.potrf_ooc(_spd(rng, n), panel_cols=w)
    att = xprof.attribute_run()
    assert att["records"] == ledger.count()
    assert att["total_wall_s"] > 0
    assert set(att["buckets"]) <= {"kernel", "collective_wait",
                                   "staging", "cache_stall", "idle"}
    # the split is exhaustive: buckets sum to the total wall
    assert abs(sum(att["buckets"].values())
               - att["total_wall_s"]) < 1e-3
    assert att["by_host"][0]["wall_s"] > 0
    assert "potrf_ooc" in att["by_op"]
    assert att["top_panels"][0]["wall_s"] >= \
        att["top_panels"][-1]["wall_s"]
    # the final drain record (step == nt) is not a panel and never
    # appears in the slowest-panels ranking
    assert all(p["step"] < n // w for p in att["top_panels"])
    snap = obs.snapshot()
    assert snap["ledger"]["records"] == att["records"]
    assert "health" not in snap           # watchdog stayed silent
    rep = obs.report()
    assert "critical path (flight recorder" in rep
    assert "kernel" in rep


def test_report_warns_on_dropped_events(flight_clean, monkeypatch):
    obs.enable()
    obs_events.instant("x")
    monkeypatch.setattr(obs_events, "_dropped", 3)
    rep = obs.report()
    assert "WARNING: 3 events were dropped" in rep


def test_export_ledger_counter_tracks(rng, flight_clean, tmp_path):
    obs.enable()
    ledger.enable()
    ooc.potrf_ooc(_spd(rng, 96), panel_cols=32)
    tr = export.chrome_trace()
    counters = [e for e in tr["traceEvents"]
                if e.get("name", "").startswith("ledger:")]
    assert counters
    assert all(e["ph"] == "C" for e in counters)
    names = {e["name"] for e in counters}
    assert "ledger:potrf_ooc:factor" in names
    # include_ledger=False keeps the pre-ledger export byte shape
    tr2 = export.chrome_trace(include_ledger=False)
    assert not any(e.get("name", "").startswith("ledger:")
                   for e in tr2["traceEvents"])
    path = export.write_trace(str(tmp_path / "t.json"))
    with open(path) as f:
        json.load(f)                       # valid JSON round trip


def test_export_without_ledger_unchanged(flight_clean):
    """Recorder off (the frozen default): the export carries zero
    ledger tracks — byte-identical to the pre-ledger layout."""
    obs.enable()
    obs_events.instant("y")
    tr = export.chrome_trace()
    assert not any(e.get("name", "").startswith("ledger:")
                   for e in tr["traceEvents"])
