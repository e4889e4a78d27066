"""Elastic mesh: throughput-driven panel re-ownership for the sharded
OOC stream (ISSUE 19 tentpole).

The sharded drivers' :class:`~.shard_ooc.CyclicSchedule` is static —
panel ownership is arithmetic on the panel index, fixed before the
stream starts. One slow host therefore rate-limits every epoch: the
fast hosts finish their trailing updates and then sit in
``bcast_wait`` until the straggler's factor frame lands (BLASX's
observation, PAPERS.md — dynamic work assignment beats static
distribution exactly on heterogeneous fleets, and the pod-scale
regime of "Large Scale Distributed Linear Algebra With TPUs" makes
stragglers the norm). PR 17 made ownership an input to graph
*construction* (sched/policies.sharded_stream), so re-owning panels
is a re-label-and-rebuild of the remaining subgraph, not surgery on a
hand-written walk. This module supplies the pieces:

* :class:`ElasticSchedule` — a CyclicSchedule with an explicit
  ``owners`` table (flat mesh positions). The default table IS the
  cyclic walk, so an elastic schedule that never remaps is
  position-for-position the static one. ``remap(boundary, owners)``
  returns a new schedule that preserves every position below
  ``boundary`` — committed/factored panels are never relabeled (the
  SL902 contract).
* :class:`ThroughputTracker` — per-position effective-throughput
  EWMA over *phase-split-corrected* step walls: the sample is the
  ledger step wall minus its ``bcast_wait`` phase (obs/ledger.py),
  so time spent waiting on someone ELSE's frame never counts as this
  host's slowness. With the ledger off the sample degrades to the
  segment wall minus the broadcaster's wait-seconds delta.
* :func:`agree_speeds` — the SPMD agreement step: every host
  contributes its own measured wall at its mesh positions through a
  psum add over a zero-padded matrix (the ``_agree_epoch`` transport
  shape; exact, because every position has exactly one nonzero
  contributor), so every host derives the IDENTICAL speed vector and
  therefore the identical remap plan — no coordinator, no extra
  protocol.
* :func:`plan_remap` — the deterministic planner: below the
  ``mesh/remap_threshold`` max/min speed ratio it returns None (a
  uniform fleet never remaps, which is what keeps the elastic route
  bitwise vs static), otherwise a deficit-greedy quota assignment of
  the not-yet-factored panels proportional to speed, with
  keep-current-owner and lowest-position tie-breaks.
* :class:`ElasticController` + :func:`run_elastic` — the segmented
  issue loop behind ``shard_ooc._run_stream``: execute the stream in
  ``mesh/remap_every``-panel segments (each a sharded_stream graph
  over the remaining panels under the CURRENT ownership map), and at
  each segment boundary measure, agree, and maybe remap before
  building the next segment. Broadcast/reduce trees, PanelCache
  residency, checkpoint commits and fault sites all follow the
  relabel because they are all derived from the schedule at graph
  construction time.

Bitwise contract: a remap changes only WHO computes — each trailing
panel still absorbs updates 0..k-1 in ascending order through the
same jitted kernels on bitwise-equal operands (fresh frames from the
broadcast, or durable-mirror replays that the resil contract already
pins bitwise), so elastic output equals static output even when
remaps fire; with uniform throughput the planner never fires and the
execution is the static stream's panel for panel.

Shrink-to-fit resume (:func:`shrink_to_fit`): a ``WorkerLost`` from a
multiproc launch no longer means a full-mesh abort — the survivors
relaunch from the durable min-epoch checkpoint (every host mirrors
every broadcast factor panel, so any survivor can replay any
committed panel) with the dead host's unfinished panels re-owned by
the survivor mesh's schedule. The rung rides the resil escalation
ladder as ``shard_shrink``, one step ABOVE ``shard_to_stream`` — it
keeps the sharded route and sheds only the lost capacity.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from ..obs import events as obs_events
from ..obs import ledger as _ledger
from ..obs import metrics as obs_metrics
from ..resil import guard as _guard
from ..tune.select import resolve as _resolve
from .shard_ooc import CyclicSchedule

#: synthetic per-position speed override (install_speeds) — the
#: deterministic test/bench hook that replaces measured throughput
_SPEEDS: Optional[List[float]] = None


def install_speeds(speeds: Optional[Sequence[float]]) -> None:
    """Install a synthetic per-position speed vector (None clears).

    Measurement and cross-host agreement are bypassed entirely: every
    host planning against the same installed vector derives the same
    remap plan, which is what makes single-process remap coverage and
    the uniform-fleet bitwise pin deterministic under CI timing noise.
    The vector must have one entry per flat mesh position."""
    global _SPEEDS
    _SPEEDS = None if speeds is None else [float(s) for s in speeds]


def installed_speeds() -> Optional[List[float]]:
    return None if _SPEEDS is None else list(_SPEEDS)


#: process-wide remap/shrink bookkeeping readable with the obs bus
#: OFF (the guard.counts mirror shape): running totals plus the last
#: remap's record. serve/admission.py attaches this to its
#: shed/degrade/reject escalation payloads so an SLO decision made
#: during mesh churn is attributable to the churn.
_remap_lock = threading.Lock()
_REMAP_STATS: Dict[str, Any] = {"remaps": 0, "panels_moved": 0,
                                "shrinks": 0, "last": None}


def remap_records() -> Dict[str, Any]:
    """Copy of the process-wide remap/shrink mirror (module comment
    above): ``remaps``/``panels_moved``/``shrinks`` totals and
    ``last`` — the most recent remap's ``{op, boundary, moved}`` (or
    None). Readable with the obs bus off."""
    with _remap_lock:
        out = dict(_REMAP_STATS)
        if out["last"] is not None:
            out["last"] = dict(out["last"])
        return out


def reset_remap_records() -> None:
    with _remap_lock:
        _REMAP_STATS.update(remaps=0, panels_moved=0, shrinks=0,
                            last=None)


class ElasticSchedule(CyclicSchedule):
    """CyclicSchedule with an explicit panel->position owner table.

    The base class derives ownership arithmetically; here the single
    source of truth is ``owners`` (flat row-major device positions,
    one per panel) and BOTH primitive queries — :meth:`owner_flat`
    and :meth:`owner_coords` — read it, so every derived query
    (owner_device/owner_process/is_mine/my_panels/update_order/
    staged_bytes) follows the table too (the SL901 contract). The
    default table is the cyclic walk itself: an un-remapped elastic
    schedule is position-for-position the static one."""

    def __init__(self, nt: int, grid, owners: Optional[Sequence[int]] = None) -> None:
        super().__init__(nt, grid)
        if owners is None:
            # the cyclic walk itself (CyclicSchedule.owner_coords
            # flattened row-major) — written out arithmetically
            # because the base methods dispatch through our override
            owners = [(k % self.p) * self.q + (k // self.p) % self.q
                      for k in range(self.nt)]
        self.owners: List[int] = [int(o) for o in owners]
        if len(self.owners) != self.nt:
            raise ValueError("owner table has %d entries for %d panels"
                             % (len(self.owners), self.nt))
        for k, o in enumerate(self.owners):
            if not 0 <= o < self.nranks:
                raise ValueError("panel %d owner %d outside the %d-"
                                 "position mesh" % (k, o, self.nranks))

    def owner_flat(self, k: int) -> int:
        return self.owners[k]

    def owner_coords(self, k: int):
        f = self.owners[k]
        return f // self.q, f % self.q

    def remap(self, boundary: int,
              owners: Sequence[int]) -> "ElasticSchedule":
        """New schedule under `owners`, preserving every position
        below `boundary` — factored/committed panels are never
        relabeled (their frames are already broadcast and mirrored;
        a relabel would orphan checkpoint bookkeeping)."""
        owners = [int(o) for o in owners]
        if owners[:boundary] != self.owners[:boundary]:
            raise ValueError(
                "remap at boundary %d would relabel an already-"
                "factored panel" % boundary)
        return ElasticSchedule(self.nt, self.grid, owners)


class ThroughputTracker:
    """Per-position effective-throughput EWMA (module doc).

    ``observe(pos, wall)`` folds one effective step-wall sample
    (seconds of OWN work — comms waits already subtracted) into
    position ``pos``'s estimate; ``walls()`` is the current estimate
    vector (None where no sample has landed yet)."""

    def __init__(self, nranks: int, alpha: float) -> None:
        self.nranks = int(nranks)
        self.alpha = min(max(float(alpha), 1e-6), 1.0)
        self._ewma: List[Optional[float]] = [None] * self.nranks

    def observe(self, pos: int, wall: float) -> None:
        wall = max(float(wall), 0.0)
        prev = self._ewma[pos]
        self._ewma[pos] = wall if prev is None \
            else self.alpha * wall + (1.0 - self.alpha) * prev

    def walls(self) -> List[Optional[float]]:
        return list(self._ewma)


#: compiled per-mesh psum for agree_speeds — built once per mesh so
#: every boundary after the first reuses the cached executable (the
#: per-boundary agreement must cost milliseconds, not a retrace)
_AGREE_FN_CACHE: Dict[Any, Any] = {}


def _agree_reduce_fn(mesh):
    fn = _AGREE_FN_CACHE.get(mesh)
    if fn is None:
        import jax
        from jax.sharding import PartitionSpec as P
        from ..parallel.smap import shard_map
        fn = shard_map(lambda xs: jax.lax.psum(xs, ("p", "q")),
                       mesh=mesh, in_specs=P(("p", "q"), None),
                       out_specs=P(), check_vma=False)
        _AGREE_FN_CACHE[mesh] = fn
    return fn


def agree_speeds(grid, my_wall: float) -> List[float]:
    """Mesh-agreed per-position speed vector (module doc).

    Every host contributes its measured effective step wall at each
    of ITS flat positions; positions are disjoint across hosts, so an
    add-reduction over zero-padded rows yields the identical full
    vector everywhere (the ``_agree_epoch`` transport shape with add
    instead of min). The reduction is a plain ``psum``, not the
    explicit ppermute tree: each position has exactly ONE nonzero
    contribution, so any reduction order adds zeros to it and the
    result is exact — and for an nranks^2 f32 control payload the
    tree's per-round dispatch dominates its schedule on every
    backend (~40x on a 2-process gloo mesh). Speed = 1/wall,
    normalized so the fastest position is 1.0. Single-process meshes
    short-circuit (every position is this host)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    devs = list(grid.mesh.devices.flat)
    nranks = len(devs)
    wall = max(float(my_wall), 1e-9)
    if len({d.process_index for d in devs}) == 1:
        walls = np.full(nranks, wall)
    else:
        me = jax.process_index()
        shards = []
        for f, d in enumerate(devs):
            row = np.zeros((1, nranks), np.float32)
            if d.process_index == me:
                row[0, f] = wall
                shards.append(jax.device_put(jnp.asarray(row), d))
        sharding = NamedSharding(grid.mesh, P(("p", "q"), None))
        garr = jax.make_array_from_single_device_arrays(
            (nranks, nranks), sharding, shards)
        out = _agree_reduce_fn(grid.mesh)(garr)
        walls = np.asarray(out.addressable_data(0),
                          np.float64).reshape(-1)[:nranks]
        walls = np.maximum(walls, 1e-9)
    speeds = 1.0 / walls
    return list(speeds / speeds.max())


def plan_remap(owners: Sequence[int], boundary: int,
               speeds: Sequence[float], threshold: float,
               positions: Optional[Sequence[int]] = None
               ) -> Optional[List[int]]:
    """Deterministic re-ownership plan, or None to keep the map.

    Only panels at or past `boundary` (not yet factored) are
    eligible; `positions` restricts the candidate owners (the
    shrink-to-fit path drops the lost host's). The threshold gate
    runs first: below a `threshold` max/min speed ratio the current
    map stands — UNLESS a remaining panel's owner is not a candidate
    (a lost host), which forces a plan regardless. Past the gate,
    each candidate gets a quota proportional to its speed and panels
    are walked in ascending order: a panel keeps its current owner
    while that owner is under quota, otherwise it moves to the
    largest-deficit candidate (lowest position on ties). Everything
    is pure arithmetic on the inputs — every host planning from the
    same (owners, boundary, speeds) derives the same plan."""
    nt = len(owners)
    rem = list(range(max(int(boundary), 0), nt))
    if positions is None:
        positions = list(range(len(speeds)))
    positions = sorted(set(int(p) for p in positions))
    if not rem or not positions:
        return None
    posset = set(positions)
    sp = {i: max(float(speeds[i]), 1e-12) for i in positions}
    forced = any(owners[k] not in posset for k in rem)
    if not forced and max(sp.values()) / min(sp.values()) < threshold:
        return None
    wsum = sum(sp.values())
    quota = {i: len(rem) * sp[i] / wsum for i in positions}
    assigned = {i: 0 for i in positions}
    new = list(owners)
    moved = 0
    for k in rem:
        cur = owners[k]
        if cur in posset and assigned[cur] + 1 <= quota[cur] + 1e-9:
            assigned[cur] += 1
            continue
        tgt = max(positions,
                  key=lambda i: (quota[i] - assigned[i], -i))
        assigned[tgt] += 1
        if tgt != cur:
            new[k] = tgt
            moved += 1
    return new if moved else None


def _resolve_ownership(ownership, n: int, dtype) -> bool:
    """Ownership arbitration for the sharded drivers (ISSUE 19):
    explicit ``ownership`` argument > measured ``mesh/ownership``
    tune entry > FROZEN "static" (core/methods.MethodOwnership — a
    COLD CACHE keeps the pure cyclic map bit-identically; elastic is
    earned or explicit, pinned by the bitwise pin suite). Returns
    True for the elastic route."""
    from ..core.methods import MethodOwnership, str2method
    m = ownership if ownership is not None else MethodOwnership.Auto
    if isinstance(m, str):
        m = str2method("ownership", m)
    if m is MethodOwnership.Auto:
        m = MethodOwnership.resolve(n, dtype)
    return m is MethodOwnership.Elastic


class ElasticController:
    """One driver invocation's remap state: the live
    :class:`ElasticSchedule`, the throughput tracker, and the knobs
    (``mesh/remap_every`` segment length, ``mesh/remap_threshold``
    speed-ratio gate, ``mesh/throughput_alpha`` EWMA weight — all
    FROZEN rows, tune/cache.py)."""

    def __init__(self, op: str, grid, nt: int, *, n: int,
                 dtype=None) -> None:
        self.op = op
        self.grid = grid
        self.sched = ElasticSchedule(nt, grid)
        self.every = max(int(_resolve("mesh", "remap_every",
                                      n=n, dtype=dtype)), 1)
        self.threshold = float(_resolve("mesh", "remap_threshold",
                                        n=n, dtype=dtype))
        alpha = float(_resolve("mesh", "throughput_alpha",
                               n=n, dtype=dtype))
        self.tracker = ThroughputTracker(self.sched.nranks, alpha)
        self.remaps = 0
        self.panels_moved = 0
        self._tail_name = "elastic.%s.%d" % (op, id(self))
        if _ledger.enabled():
            _ledger.tail(self._tail_name)   # set the cursor: earlier
            # runs' retained records must not seed this run's EWMA

    # -- measurement -------------------------------------------------

    def observe_segment(self, steps: int, seg_wall: float,
                        wait_delta: float,
                        first_step: int = 0) -> None:
        """Fold one segment's effective per-step wall into THIS
        host's positions. Ledger on: phase-split-corrected per-step
        walls from the tail (wall minus its ``bcast_wait`` phase —
        comms waits are the OTHER side's slowness). Ledger off: the
        segment wall minus the broadcaster's wait-seconds delta,
        averaged over the segment's steps."""
        import jax
        samples: List[float] = []
        if _ledger.enabled():
            for rec in _ledger.tail(self._tail_name):
                if rec.op != self.op or rec.step < first_step:
                    continue   # catch-up replay slots are not work
                samples.append(max(
                    rec.wall - rec.phases.get("bcast_wait", 0.0),
                    0.0))
        if not samples and steps > 0:
            samples = [max(seg_wall - wait_delta, 0.0)
                       / float(steps)]
        if not samples:
            return
        mean = sum(samples) / len(samples)
        me = jax.process_index()
        for f, d in enumerate(self.grid.mesh.devices.flat):
            if d.process_index == me:
                self.tracker.observe(f, mean)

    def speeds(self) -> List[float]:
        """The agreed (or installed) per-position speed vector."""
        if _SPEEDS is not None:
            if len(_SPEEDS) != self.sched.nranks:
                raise ValueError(
                    "installed speed vector has %d entries for a %d-"
                    "position mesh" % (len(_SPEEDS),
                                       self.sched.nranks))
            return list(_SPEEDS)
        walls = [w for w in self.tracker.walls() if w is not None]
        my_wall = sum(walls) / len(walls) if walls else 0.0
        return agree_speeds(self.grid, my_wall)

    # -- the remap decision ------------------------------------------

    def maybe_remap(self, boundary: int) -> int:
        """Plan + apply a re-ownership at `boundary`; returns the
        panel-move count (0 = map kept). Publishes the decision as a
        ``shard::remap`` instant plus the ``ooc.shard.remaps`` /
        ``ooc.shard.remap_panels_moved`` counters so every remap is
        attributable on the event bus and in the ledger."""
        speeds = self.speeds()
        plan = plan_remap(self.sched.owners, boundary, speeds,
                          self.threshold)
        if plan is None:
            return 0
        moved = sum(1 for a, b in zip(self.sched.owners, plan)
                    if a != b)
        self.sched = self.sched.remap(boundary, plan)
        self.remaps += 1
        self.panels_moved += moved
        with _remap_lock:
            _REMAP_STATS["remaps"] += 1
            _REMAP_STATS["panels_moved"] += moved
            _REMAP_STATS["last"] = {"op": self.op,
                                    "boundary": int(boundary),
                                    "moved": moved}
        if obs_events.enabled():
            obs_events.instant(
                "shard::remap", cat="shard", op=self.op,
                boundary=boundary, moved=moved,
                speeds=[round(s, 4) for s in speeds])
            obs_metrics.inc("ooc.shard.remaps")
            obs_metrics.inc("ooc.shard.remap_panels_moved", moved)
        return moved


def run_elastic(ctrl: ElasticController, *, op: str, bc, st,
                depth: int, epoch: int, factor_panels: Sequence[int],
                tail_panels: Sequence[int], payload_shape: Callable,
                make_payload: Callable, complete: Callable,
                replay: Callable, apply: Callable,
                tail_step: Optional[Callable], led, ck, eng,
                step_obs: Callable, nt: int) -> None:
    """The segmented elastic issue loop (shard_ooc._run_stream's
    elastic route; module doc).

    Each segment is a ``sharded_stream`` graph over the panels up to
    the segment boundary under the CURRENT ownership map, with
    ``applied_through`` pruning the updates earlier segments already
    applied and ``trailing_to`` extending the trailing sweep over the
    whole stream — so within a segment every trailing panel absorbs
    exactly the segment's update steps, in ascending order, through
    the driver's closures (bitwise). At each boundary
    the controller measures, agrees, and maybe remaps; panels moved
    away are dropped from this host's working set (their next owner
    stages them fresh and catches up through durable-mirror replays),
    panels moved here need nothing — the next segment's graph simply
    contains their catch-up nodes: ownership is a graph-construction
    input here, which is the whole mechanism."""
    from ..sched import policies as _policies
    from ..sched.runtime import execute as _execute
    panels = list(factor_panels)
    last = panels[-1] if panels else -1
    b0 = int(epoch)
    while True:
        b1 = min(b0 + ctrl.every, last + 1)
        final = b1 >= last + 1
        sched = ctrl.sched
        g = _policies.sharded_stream(
            op, sched=sched, bc=bc, st=st, depth=depth, epoch=b0,
            factor_panels=[p for p in panels if p < b1],
            tail_panels=(list(tail_panels) if final else []),
            payload_shape=payload_shape, make_payload=make_payload,
            complete=complete, replay=replay, apply=apply,
            tail=tail_step, applied_through=st.applied_through,
            trailing_to=nt)

        def _begin(k, _b0=b0, _sched=sched):
            if led is not None:
                led.begin(k, owner=_sched.owner_process(k),
                          epoch=_b0)

        def _end(k, _b0=b0, _b1=b1):
            if _b0 <= k < _b1:
                step_obs(k)
            if ck is not None and k >= _b0 and ck.due(k):
                eng.wait_writes()   # every panel <= k is durable;
                ck.commit(k + 1)    # the in-flight panel is NOT
            if led is not None:
                led.commit()

        t_seg = time.perf_counter()
        wait0 = bc.wait_seconds
        _execute(g, op=op, nt=nt, begin_step=_begin, end_step=_end)
        if final:
            break
        # trailing panels are applied through b1 now; factored
        # panels leave the in-flight bookkeeping
        for j in ctrl.sched.my_panels():
            if j >= b1:
                st.upto[j] = b1
        for p in range(b0, b1):
            st.upto.pop(p, None)
        ctrl.observe_segment(b1 - b0,
                             time.perf_counter() - t_seg,
                             bc.wait_seconds - wait0,
                             first_step=b0)
        if ctrl.maybe_remap(b1):
            for j in sorted(st.staged):
                if j >= b1 and not ctrl.sched.is_mine(j):
                    st.discard(j)
                    st.staged.discard(j)
                    st.upto.pop(j, None)
        b0 = b1
    if ck is not None and ck.epoch < nt:
        eng.wait_writes()
        ck.commit(nt)


def shrink_to_fit(primary: Callable[[], Any],
                  survivors: Callable[[Any], Any], *,
                  op: str = "", **ctx) -> Any:
    """Shrink-to-fit resume (module doc): run `primary` (the full
    mesh launch); on :class:`~..resil.guard.WorkerLost` record the
    ``shard_shrink`` escalation rung and run `survivors(exc)` — the
    caller's smaller-mesh relaunch against the same checkpoint root.
    Any survivor can resume any committed panel because every host
    mirrors every broadcast factor frame (shard_ooc complete()
    contract), and the resumed schedule re-owns the dead host's
    unfinished panels by construction. Returns whichever launch
    completed."""
    try:
        return primary()
    except _guard.WorkerLost as e:
        _guard.record_escalation(
            "shard_shrink", op=op, lost_process=e.process_id,
            returncode=e.returncode, **ctx)
        with _remap_lock:
            _REMAP_STATS["shrinks"] += 1
        if obs_events.enabled():
            obs_events.instant("shard::shrink", cat="shard", op=op,
                               lost=e.process_id,
                               returncode=e.returncode)
            obs_metrics.inc("ooc.shard.shrinks")
        return survivors(e)
