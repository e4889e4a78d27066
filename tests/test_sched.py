"""Task-graph runtime (ISSUE 17): graph construction/validation, the
deterministic executor, and the acceptance pins of the streams that
issue through it — the three sharded OOC drivers BITWISE equal to the
single-engine loops at lookahead depths 0/1/2, including budget 0,
forced spills, seeded-fault determinism, and checkpoint resume from
mid-graph."""

import json

import numpy as np
import pytest

from slate_tpu.core.exceptions import SlateError
from slate_tpu.dist import shard_ooc
from slate_tpu.linalg import ooc
from slate_tpu.obs import ledger
from slate_tpu.resil import faults, guard
from slate_tpu.sched import (FAULT_SITE_OF_KIND, NODE_KINDS,
                             PHASE_OF_KIND, TaskGraph, execute)


@pytest.fixture
def obs_on():
    from slate_tpu import obs
    from slate_tpu.obs import metrics
    obs.enable()
    obs.clear()
    metrics.reset()
    yield obs
    obs.disable()
    obs.clear()
    metrics.reset()


def _spd(rng, n, dtype=np.float64):
    x = rng.standard_normal((n, n)).astype(dtype)
    return x @ x.T / n + 4.0 * np.eye(n, dtype=dtype)


# -- graph construction + validation --------------------------------------

def test_graph_rejects_unknown_kind():
    g = TaskGraph("t")
    with pytest.raises(SlateError, match="unknown node kind"):
        g.add("frobnicate", lambda: None, key=(0,))


def test_graph_rejects_cycle():
    g = TaskGraph("t")
    a = g.add("stage", lambda: None, key=(0,))
    b = g.add("factor", lambda: None, key=(1,), deps=[a])
    g.add_edge(b, a)
    with pytest.raises(SlateError, match="cycle"):
        g.validate()


def test_graph_rejects_orphan():
    g = TaskGraph("t")
    a = g.add("stage", lambda: None, key=(0,))
    g.add("factor", lambda: None, key=(1,), deps=[a])
    g.add("writeback", lambda: None, key=(2,))     # no edges at all
    with pytest.raises(SlateError, match="orphan"):
        g.validate()


def test_graph_single_node_is_valid():
    g = TaskGraph("t")
    g.add("stage", lambda: None, key=(0,))
    g.validate()                                   # no orphan check


def test_execute_order_deps_then_priority():
    """Ready nodes pop in (key, seq) min-order; dependencies override
    priority — a low-key node waits until its dep completes."""
    order = []
    g = TaskGraph("t")
    late = g.add("factor", lambda: order.append("f9"), key=(9,))
    # key (0,) but gated on the key-(9,) node: runs LAST
    g.add("update", lambda: order.append("u0"), key=(0,),
          deps=[late])
    a = g.add("stage", lambda: order.append("s1"), key=(1,))
    g.add("writeback", lambda: order.append("w2"), key=(2,),
          deps=[a])
    execute(g, op="t")
    assert order == ["s1", "w2", "f9", "u0"]


def test_execute_slot_hooks_bracket_slots():
    begins, ends = [], []
    g = TaskGraph("t")
    a = g.add("stage", lambda: None, key=(0, 0))
    b = g.add("factor", lambda: None, key=(0, 1), deps=[a])
    g.add("writeback", lambda: None, key=(2, 0), deps=[b])
    execute(g, op="t", nt=3, begin_step=begins.append,
            end_step=ends.append)
    assert begins == [0, 2]         # empty slot 1 never opens
    assert ends == [0, 2]


def test_execute_detects_deadlock_on_key_misuse():
    """A dep whose producer never becomes ready (cycle) is a loud
    deadlock assertion, not a silent partial run."""
    g = TaskGraph("t")
    a = g.add("stage", lambda: None, key=(0,))
    b = g.add("factor", lambda: None, key=(1,), deps=[a])
    g.add_edge(b, a)
    with pytest.raises(SlateError):
        execute(g, op="t")


def test_kind_tables_total_and_on_vocabulary():
    """The SL701/SL702 contract, asserted live: every kind has a
    ledger phase and a fault-site entry, and values come from the
    registered vocabularies."""
    assert set(PHASE_OF_KIND) == set(NODE_KINDS)
    assert set(FAULT_SITE_OF_KIND) == set(NODE_KINDS)
    assert set(PHASE_OF_KIND.values()) <= set(ledger.PHASES)
    assert {s for s in FAULT_SITE_OF_KIND.values()
            if s is not None} <= set(faults.SITES)


# -- the sharded streams issue through the graph ---------------------------

def _shard_case(rng, op):
    """(driver, operand, factor panels) of one sharded op at the
    pins' shape: n=160, w=32, and for QR/LU the m<n shape whose last
    two panels ride the graph's tail bcast nodes."""
    if op == "potrf":
        return shard_ooc.shard_potrf_ooc, _spd(rng, 160), 5
    g = rng.standard_normal((96, 160))
    if op == "geqrf":
        return shard_ooc.shard_geqrf_ooc, g, 3
    return shard_ooc.shard_getrf_ooc, \
        g * (1.0 + np.arange(96))[:, None], 3


def _node_count(sched, nf):
    """Nodes of one sharded stream by CyclicSchedule's own walk: an
    update a (step, owned trailing panel) pair, a stage a panel that
    takes any, factor (owner only) + bcast + writeback a factor
    panel, one bcast a tail panel."""
    sweeps = [sched.update_order(k) for k in range(nf)]
    return (sum(len(u) for u in sweeps)
            + len({p for u in sweeps for p in u})
            + sum(1 for k in range(nf) if sched.is_mine(k))
            + 2 * nf + (sched.nt - nf))


@pytest.mark.parametrize("depth", [0, 2])
@pytest.mark.parametrize("op", ["potrf", "geqrf", "getrf"])
def test_shard_stream_issues_one_graph(rng, grid8, obs_on, op, depth):
    """Every sharded driver runs ONE graph whose node count is the
    schedule's at every lookahead depth: depth is where nodes are
    keyed, never which nodes there are."""
    from slate_tpu.obs import metrics
    fn, a, nf = _shard_case(rng, op)
    fn(a, grid8, panel_cols=32, lookahead=depth)
    c = metrics.snapshot()["counters"]
    assert c.get("sched.graphs") == 1
    assert c.get("sched.nodes_issued") == _node_count(
        shard_ooc.CyclicSchedule(5, grid8), nf)


def test_graph_issue_counters(rng, grid8, obs_on):
    """sched.* counters: one graph, every node issued, overhead wall
    accrued."""
    from slate_tpu.obs import metrics
    shard_ooc.shard_potrf_ooc(_spd(rng, 96), grid8, panel_cols=32)
    c = metrics.snapshot()["counters"]
    assert c.get("sched.graphs") == 1
    # nt=3, one process owning every panel: 2 stage + 3 update
    # (2+1+0) + 3 factor + 3 bcast + 3 writeback
    assert c.get("sched.nodes_issued") == 14
    assert c.get("sched.nodes_issued") == _node_count(
        shard_ooc.CyclicSchedule(3, grid8), 3)
    assert c.get("sched.issue_overhead_seconds", 0) >= 0


# -- sharded bitwise pins (8-virtual-device mesh) -------------------------

@pytest.mark.slow
def test_shard_potrf_bitwise_depths(rng, grid8):
    """The acceptance pin: the sharded factor is the single-engine
    loop's at depths 0/1/2, budget 0 AND a forced-spill budget."""
    n, w = 160, 32
    a = _spd(rng, n)
    L0 = np.asarray(ooc.potrf_ooc(a, panel_cols=w))
    for depth in (0, 1, 2):
        for budget in (0, int(1.5 * n * w * 8)):
            L = shard_ooc.shard_potrf_ooc(
                a, grid8, panel_cols=w, lookahead=depth,
                cache_budget_bytes=budget)
            assert np.array_equal(L0, np.asarray(L)), \
                "depth %d budget %d" % (depth, budget)


@pytest.mark.slow
def test_shard_geqrf_getrf_bitwise_depths(rng, grid8):
    """Same pin for QR and tournament LU, including the m<n shapes
    whose tail panels ride the graph's tail bcast nodes."""
    w = 32
    for shape in ((160, 160), (96, 160)):
        g = rng.standard_normal(shape)
        lp = g * (1.0 + np.arange(shape[0]))[:, None]
        q0, t0 = ooc.geqrf_ooc(g, panel_cols=w)
        l0, p0 = ooc.getrf_tntpiv_ooc(lp, panel_cols=w)
        for depth in (0, 1, 2):
            q, t = shard_ooc.shard_geqrf_ooc(
                g, grid8, panel_cols=w, lookahead=depth)
            assert np.array_equal(np.asarray(q0), np.asarray(q))
            assert np.array_equal(np.asarray(t0), np.asarray(t))
            lu, piv = shard_ooc.shard_getrf_ooc(
                lp, grid8, panel_cols=w, lookahead=depth)
            assert np.array_equal(np.asarray(l0), np.asarray(lu))
            assert np.array_equal(np.asarray(p0), np.asarray(piv))


@pytest.mark.slow
def test_shard_staging_exact_and_ahead(rng, grid8, obs_on):
    """The stream stages exactly the schedule's prediction
    (depth-invariant bytes) and dispatches nt-1 frames ahead at
    depth 1."""
    from slate_tpu.obs import metrics
    n, w, item = 160, 32, 8
    nt = (n + w - 1) // w
    a = _spd(rng, n)
    sched = shard_ooc.CyclicSchedule(nt, grid8)
    expect = sched.staged_bytes({k: n - k * w for k in range(nt)},
                                w, n - (nt - 1) * w, item, depth=1)
    metrics.reset()
    shard_ooc.shard_potrf_ooc(a, grid8, panel_cols=w, lookahead=1,
                              cache_budget_bytes=64 * n * w * item)
    c = metrics.snapshot()["counters"]
    assert int(c["ooc.h2d_bytes"]) == expect
    assert int(c["ooc.shard.bcast_ahead"]) == nt - 1


# -- seeded-fault determinism ---------------------------------------------

@pytest.mark.slow
def test_shard_step_faults_fire_in_same_order(rng, grid8):
    """The per-panel step check fires once a panel in ascending order
    at every depth: a plan that skips two matches and fires on the
    third dies at step 2, third occurrence, with the same log at
    depths 0, 1 and 2 — so seeded plans are depth-invariant."""
    a = _spd(rng, 160)

    def run(depth):
        plan = faults.install(faults.FaultPlan(
            [{"site": "step", "match": {"op": "shard_potrf_ooc"},
              "after": 2, "times": 1}], seed=7))
        with pytest.raises(faults.InjectedFault) as e:
            shard_ooc.shard_potrf_ooc(a, grid8, panel_cols=32,
                                      lookahead=depth)
        faults.clear()
        return (e.value.site, e.value.ctx.get("step"),
                e.value.occurrence), plan.log()

    r0, log0 = run(0)
    assert r0 == ("step", 2, 2)
    assert (r0, log0) == run(1) == run(2)


# -- checkpoint/resume from mid-graph -------------------------------------

@pytest.mark.slow
def test_shard_crash_resume_bitwise(rng, grid8, tmp_path):
    """Sharded, depth 2: resume FROM MID-GRAPH — the rebuilt graph's
    replay writebacks feed the surviving update chain, landing
    bitwise on the uninterrupted factor."""
    a = _spd(rng, 160)
    L0 = np.asarray(shard_ooc.shard_potrf_ooc(a, grid8,
                                              panel_cols=32))
    faults.install(faults.FaultPlan(
        [{"site": "step",
          "match": {"op": "shard_potrf_ooc", "step": 3},
          "times": 1}]))
    with pytest.raises(faults.InjectedFault):
        shard_ooc.shard_potrf_ooc(a, grid8, panel_cols=32,
                                  lookahead=2,
                                  ckpt_path=str(tmp_path),
                                  ckpt_every=1)
    faults.clear()
    epoch = json.loads(
        (tmp_path / "host0" / "meta.json").read_text())["epoch"]
    assert 0 < epoch <= 3               # mid-run, commit trails issue
    L1 = np.asarray(shard_ooc.shard_potrf_ooc(
        a, grid8, panel_cols=32, lookahead=2,
        ckpt_path=str(tmp_path), ckpt_every=1))
    assert np.array_equal(L0, L1)
    # cross-depth resume parity: a synchronous run's crash resumed
    # at depth 1 lands on the same factor too
    g = rng.standard_normal((160, 160))
    qr0, tau0 = shard_ooc.shard_geqrf_ooc(g, grid8, panel_cols=32)
    faults.install(faults.FaultPlan(
        [{"site": "step",
          "match": {"op": "shard_geqrf_ooc", "step": 2},
          "times": 1}]))
    ck2 = tmp_path / "qr"
    with pytest.raises(faults.InjectedFault):
        shard_ooc.shard_geqrf_ooc(g, grid8, panel_cols=32,
                                  ckpt_path=str(ck2), ckpt_every=1)
    faults.clear()
    qr1, tau1 = shard_ooc.shard_geqrf_ooc(
        g, grid8, panel_cols=32, lookahead=1, ckpt_path=str(ck2),
        ckpt_every=1)
    assert np.array_equal(np.asarray(qr0), np.asarray(qr1))
    assert np.array_equal(np.asarray(tau0), np.asarray(tau1))
