"""Graph executor.

:func:`execute` drives a validated :class:`~.graph.TaskGraph` to
completion. The graph's nodes are closures over the sharded drivers'
own code (jitted kernels, engines, broadcaster, fault sites, ledger),
so the runtime owns only *order*, never semantics.

Deterministic tie-breaking: ready nodes sit in a min-heap keyed
``(node.key, node.seq)`` and exactly one runs at a time, so the issue
order is a function of the graph alone: two runs of one graph issue
the same nodes in the same sequence, and the policy's keys make every
lookahead depth apply each panel's updates in ascending step order —
which is what keeps results BITWISE equal across depths and to the
single-engine loops (the bitwise pin suite holds this per op and per
depth).

Slot bookkeeping: ``key[0]`` is the node's *slot* (the panel-step it
belongs to). On each slot transition the runtime calls
``end_step(prev_slot)`` then heartbeats the stall watchdog
(obs/health.py — one beat a step, the cadence of the single-engine
loops) then ``begin_step(slot)`` — drivers hang their
``led.begin``/``led.commit``/checkpoint-commit bracketing off these
hooks, so ledger records and checkpoint epochs advance a step at a
time. Each node's closure runs inside
``_ledger.frame(PHASE_OF_KIND[node.kind])`` (frames nest with
self-time semantics, so inner frames inside the closures still
attribute correctly and sums stay exhaustive).

Issue-loop overhead is observable: ``sched.nodes_issued`` counts
nodes, ``sched.issue_overhead_seconds`` accrues loop wall minus node
wall (the pure scheduling cost), ``sched.graphs`` the graphs run.
"""

from __future__ import annotations

import heapq
import time
from typing import Callable, Optional

from ..core.exceptions import slate_assert
from ..obs import events as obs_events
from ..obs import health as _health
from ..obs import ledger as _ledger
from ..obs import metrics as obs_metrics
from .graph import PHASE_OF_KIND, TaskGraph


def execute(graph: TaskGraph, *, op: str,
            nt: Optional[int] = None,
            begin_step: Optional[Callable[[int], None]] = None,
            end_step: Optional[Callable[[int], None]] = None) -> None:
    """Run every node of `graph` in dependency + priority order.

    `op` names the driver for watchdog heartbeats; `nt` is the total
    slot count (progress denominator). `begin_step`/`end_step` fire
    on slot transitions (slot = ``node.key[0]``), bracketing all the
    nodes that share a slot — the graph analogue of one iteration of
    a panel loop.
    """
    graph.validate()
    nin = {n: n._nin for n in graph.nodes}
    heap = [(n.key, n.seq, n) for n in graph.nodes if nin[n] == 0]
    heapq.heapify(heap)

    obs_on = obs_events.enabled()
    t_loop = time.perf_counter() if obs_on else 0.0
    t_nodes = 0.0
    executed = 0
    cur_slot: Optional[int] = None
    # On exception (e.g. an injected step fault) the in-flight slot's
    # end_step does NOT fire: led.commit and the checkpoint commit
    # are skipped for an interrupted step, as in the single-engine
    # loops.
    while heap:
        _key, _seq, node = heapq.heappop(heap)
        slot = node.key[0] if node.key else 0
        if slot != cur_slot:
            if cur_slot is not None and end_step is not None:
                end_step(cur_slot)
            _health.heartbeat(op, slot, nt)
            if begin_step is not None:
                begin_step(slot)
            cur_slot = slot
        if obs_on:
            t0 = time.perf_counter()
        with _ledger.frame(PHASE_OF_KIND[node.kind]):
            node.run()
        if obs_on:
            t_nodes += time.perf_counter() - t0
        executed += 1
        for m in node._outs:
            nin[m] -= 1
            if nin[m] == 0:
                heapq.heappush(heap, (m.key, m.seq, m))
    slate_assert(
        executed == len(graph.nodes),
        "%r graph deadlocked: %d of %d nodes never became ready"
        % (op, len(graph.nodes) - executed, len(graph.nodes)))
    if cur_slot is not None and end_step is not None:
        end_step(cur_slot)
    if obs_on:
        obs_metrics.inc("sched.nodes_issued", executed)
        obs_metrics.inc(
            "sched.issue_overhead_seconds",
            max(time.perf_counter() - t_loop - t_nodes, 0.0))
        obs_metrics.inc("sched.graphs")
