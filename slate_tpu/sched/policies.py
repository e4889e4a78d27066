"""The graph constructor of the sharded streams.

:func:`sharded_stream` builds the :class:`~.graph.TaskGraph` of one
sharded out-of-core factorization (shard_potrf/geqrf/getrf_ooc over a
CyclicSchedule). Its node closures call the driver's own closures —
same engines, same jitted kernels, same broadcaster, same
guard/fault/ledger calls — and its ``key`` tuples fix the order in
which the executor issues nodes that are ready together (runtime.py
doc). Lookahead is a PURE GRAPH PROPERTY: depth d only changes which
slot a panel's factor/bcast nodes are keyed at (``max(i-d, 0)``) and
how many trailing updates ride the promoted window — the dependency
structure itself (bcast -> writeback -> consuming updates) never
changes, and no node closure consults the depth. A record's consumers
are explicit edges, not a per-panel high-water counter.

Slot/key layout (cls column is the intra-slot ordering class)::

    node            slot                     cls
    writeback i     i (d=0) | max(i-d+1, 0)  0   realize record i
    promote U(j,s)  max(j-d, 0)              1   window catch-up
    factor i        max(i-d, 0)              2   owner panel factor
    bcast i         max(i-d, 0)              3   collective dispatch
    sweep U(j,s)    s                        4   trailing sweep
    tail k          k                        0   m<n tail broadcast

Stage nodes (first-touch H2D of a trailing panel) share their first
update's key prefix with a trailing 0, so they pop immediately before
it. The per-panel ``step`` fault check fires exactly once per panel
from the first node that processes it, in ascending panel order at
every depth, so seeded fault plans stay deterministic
(resil/faults.py contract).
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, Callable, Dict, Optional, Sequence

from ..obs import events as obs_events
from ..obs import ledger as _ledger
from ..obs import metrics as obs_metrics
from ..resil import faults as _faults
from .graph import TaskGraph


def sharded_stream(op: str, *, sched, bc, st, depth: int, epoch: int,
                   factor_panels: Sequence[int],
                   tail_panels: Sequence[int],
                   payload_shape: Callable,
                   make_payload: Callable,
                   complete: Callable,
                   replay: Callable,
                   apply: Callable,
                   tail: Optional[Callable[[int], None]] = None,
                   applied_through: Optional[Callable[[int], int]]
                   = None,
                   trailing_to: Optional[int] = None) -> TaskGraph:
    """The sharded right-looking stream as a graph (module doc
    table).

    Takes the driver's closures (payload_shape / make_payload /
    complete / replay / apply — dist/shard_ooc.py _run_stream doc)
    plus the driver's `tail(k)` body for the m<n tail panels.
    `sched` is the CyclicSchedule, `bc` the PanelBroadcaster, `st` the
    _ShardState working set, `depth` the lookahead, `epoch` the agreed
    resume epoch.

    Segmented construction (ISSUE 19, dist/elastic.py): the elastic
    route builds the stream as a SEQUENCE of these graphs, one per
    remap segment. `applied_through(p)` is the first update step
    panel p has NOT yet absorbed (earlier segments' updates are
    pruned — node and consumer count both), and `trailing_to`
    extends the trailing-update sweep past the factor range so
    panels factoring in LATER segments stay caught up. Replay
    writeback nodes below the epoch materialize only when some
    pruned-aware consumer still needs their record, which keeps the
    per-segment replay H2D proportional to actual catch-up instead
    of O(nt^2) across segments. Defaults (None/None) give the
    unsegmented construction."""
    d = max(int(depth), 0)
    ep = int(epoch)
    at = applied_through if applied_through is not None \
        else (lambda _p: 0)
    last = factor_panels[-1] if len(factor_panels) else -1
    g = TaskGraph(op)

    # --- shared bookkeeping the node closures close over ------------
    checked: set = set()
    recs: Dict[int, Any] = {}       # realized update records
    payloads: Dict[int, Any] = {}   # factor -> bcast handoff
    frames: Dict[int, Any] = {}     # bcast -> writeback handoff
    sj: Dict[int, Any] = {}         # stage -> first-update handoff

    def _chk(k: int) -> None:
        if k not in checked:
            checked.add(k)
            # `mine`: this host owns the panel — elastic straggler
            # plans (ISSUE 19) scope their slowdown to owned work so
            # a re-ownership actually sheds the injected cost
            _faults.check("step", op=op, step=k,
                          mine=bool(sched.is_mine(k)))

    mine_tr = sorted(j for j in sched.my_panels()
                     if j >= max(1, ep))
    tail_set = set(tail_panels)

    # explicit per-record consumer counts: a record dies when its
    # last consuming update ran (the slot-s sweep is always the last
    # use)
    remaining: Dict[int, int] = {}
    for j in mine_tr:
        for s in range(at(j), min(j, last + 1)):
            remaining[s] = remaining.get(s, 0) + 1

    def slot_wb(i: int) -> int:
        return i if d == 0 else max(i - d + 1, 0)

    def slot_issue(i: int) -> int:
        return max(i - d, 0)

    def ahead(i: int) -> bool:
        # only depth 0 and the very first panel issue synchronously;
        # everything else is dispatched ahead — what the
        # ooc.shard.bcast_ahead pin counts
        return d > 0 and not (i == 0 and ep == 0)

    def _promo(p: int, s: int) -> bool:
        # promoted window catch-up vs trailing sweep: factor panels
        # absorb their last d steps at issue time, everything else
        # sweeps at the record's slot
        return p <= last and d > 0 and s >= p - d

    # slot-0 sweep prefetch chain: every owned
    # trailing panel first-touches at slot 0 — promoted panels stage
    # synchronously inside the window, sweep panels chain exact
    # prefetches in sweep order (window tails first, then ascending)
    sweep0 = sorted((p for p in mine_tr if not _promo(p, 0)),
                    key=lambda p: (0 if p <= d else 1, p))
    pref_of = {sweep0[i]: sweep0[i + 1]
               for i in range(len(sweep0) - 1)}

    # --- node closures ----------------------------------------------
    def _run_stage(p: int) -> None:
        sj[p] = st.take(p)

    def _run_update(p: int, s: int, promo: bool,
                    pref: Optional[int]) -> None:
        if promo:
            _chk(p)
        t0 = time.perf_counter()
        with _ledger.frame("stage"):
            S = sj.pop(p, None)
            if S is None:
                S = st.take(p)
        if pref is not None:
            st.prefetch_panel(pref)
        r = recs[s]
        if promo:
            with obs_events.span("shard::update", cat="shard",
                                 panel=p, step=s, ahead=True), \
                    _ledger.frame("update"):
                S = apply(S, r, p)
        else:
            with obs_events.span("shard::update", cat="shard",
                                 panel=p, step=s), \
                    _ledger.frame("update"):
                S = apply(S, r, p)
        st.stash(p, S)
        remaining[s] -= 1
        if remaining[s] <= 0:
            recs.pop(s, None)
        if not promo:
            obs_metrics.inc("ooc.shard.update_seconds",
                            time.perf_counter() - t0)

    def _run_factor(i: int) -> None:
        _chk(i)
        with _ledger.frame("stage"):
            S = st.take(i)
        with obs_events.span("shard::factor", cat="shard", panel=i,
                             ahead=ahead(i)), _ledger.frame("factor"):
            payloads[i] = make_payload(i, S)
        st.discard(i)

    def _run_bcast(i: int) -> None:
        _chk(i)
        shape, dtype = payload_shape(i)
        frames[i] = bc.broadcast_async(
            payloads.pop(i, None), sched.owner_flat(i), shape, dtype,
            panel=i, ahead=ahead(i))

    def _run_wb(i: int) -> None:
        _chk(i)
        recs[i] = complete(i, bc.complete(frames.pop(i)))
        if remaining.get(i, 0) <= 0:
            recs.pop(i, None)

    def _run_replay(i: int) -> None:
        _chk(i)
        recs[i] = replay(i)
        if remaining.get(i, 0) <= 0:
            recs.pop(i, None)

    def _run_tail(k: int) -> None:
        _chk(k)
        if k < ep:
            return          # durable on resume
        tail(k)

    # --- assembly (ascending panel order, so every dep exists) ------
    mine_set = set(mine_tr)
    wbn: Dict[int, Any] = {}
    un_last: Dict[int, Any] = {}
    prev_tail = None
    npanels = (tail_panels[-1] + 1) if len(tail_panels) else (last + 1)
    if trailing_to is not None:
        npanels = max(npanels, int(trailing_to))
    for p in range(npanels):
        if p in mine_set:
            prev = None
            for s in range(at(p), min(p, last + 1)):
                promo = _promo(p, s)
                if promo:
                    key = (max(p - d, 0), 1, p, s, 1)
                else:
                    key = (s, 4, 0 if p <= s + d else 1, p, 1)
                if prev is None:
                    prev = g.add("stage", partial(_run_stage, p),
                                 panel=p,
                                 owner=sched.owner_flat(p),
                                 key=key[:-1] + (0,))
                prev = g.add(
                    "update",
                    partial(_run_update, p, s, promo,
                            pref_of.get(p) if s == 0 else None),
                    panel=p, step=s, owner=sched.owner_flat(s),
                    key=key, deps=[prev, wbn.get(s)])
            un_last[p] = prev
        if p <= last:
            owner = sched.owner_flat(p)
            if p >= ep:
                fnode = None
                if sched.is_mine(p):
                    fnode = g.add("factor", partial(_run_factor, p),
                                  panel=p, owner=owner,
                                  key=(slot_issue(p), 2, p, 0, 0),
                                  deps=[un_last.get(p)])
                bnode = g.add("bcast", partial(_run_bcast, p),
                              panel=p, owner=owner,
                              key=(slot_issue(p), 3, p, 0, 0),
                              deps=[fnode, wbn.get(p - 1)])
                wbn[p] = g.add("writeback", partial(_run_wb, p),
                               panel=p, owner=owner,
                               key=(slot_wb(p), 0, p, 0, 0),
                               deps=[bnode, wbn.get(p - 1)])
            elif applied_through is None or remaining.get(p, 0) > 0:
                # segmented construction: replay only records a
                # pruned-aware consumer still needs (catch-up
                # panels); the unsegmented route keeps every replay
                # node, so the fault-check sequence stays once each
                # in ascending order
                wbn[p] = g.add("writeback", partial(_run_replay, p),
                               panel=p, owner=owner,
                               key=(slot_wb(p), 0, p, 0, 0),
                               deps=[wbn.get(p - 1)])
        elif p in tail_set:
            prev_tail = g.add("bcast", partial(_run_tail, p),
                              panel=p, owner=sched.owner_flat(p),
                              key=(p, 0, p, 0, 0),
                              deps=[un_last.get(p), wbn.get(last),
                                    prev_tail])
    return g
