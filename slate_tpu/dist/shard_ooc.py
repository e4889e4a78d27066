"""Sharded out-of-core execution layer (ISSUE 7 tentpole): the
composition of dist/'s explicit-schedule tree engine and the
linalg/stream.py panel-residency engine — the SLATE distribution model
(PAPER.md §1) carried to the beyond-HBM regime.

The two existing halves each cap the problem size at one device's
pipe: dist/ shards IN-HBM problems across a mesh, and stream.py
streams BEYOND-HBM problems host<->one device. Composed, panels are
assigned **2D-block-cyclically to mesh positions** and each host's
StreamEngine stages only its local shard's panels — the aggregate
host-RAM/HBM pipe of the whole pod, which is exactly how "Large Scale
Distributed Linear Algebra With TPUs" (PAPERS.md) reaches
beyond-single-chip n (and JAXMg shows carries to GPU meshes with
different constants).

Schedule shape (right-looking, the reference's potrf.cc/geqrf.cc panel
loop):

  * ``CyclicSchedule`` — panel k of the column stream is owned by the
    mesh position reached by the column-major cyclic walk
    ``(k mod p, (k // p) mod q)`` (the GridOrder.Col convention of
    parallel/mesh.py; the diagonal-ownership walk of the SLATE
    2D-block-cyclic tile map at panel granularity — tile-level row
    distribution within a panel column is the further step). Ownership
    is STATIC, so every host knows, before the stream starts, exactly
    which panels it will stage and in what order — prefetch becomes
    exact rather than heuristic (asserted by test via the obs h2d
    counters: an eviction-free run stages precisely the owned inputs,
    nothing else).
  * per step k: the owner factors its panel in-core (the SAME jitted
    panel kernels as the single-device stream), then ``PanelBroadcaster``
    replicates the factor panel over the dist/tree.py ppermute combine
    tree — payload on the owner's device, exact zeros elsewhere, a
    log-depth add-combine (x + 0 is exact, the dist/tuneshare
    transport shape carried to float panels; fan-in is the
    ``ooc/shard_fanin`` tunable and the scheduled ppermute count lands
    in the obs comms accounting like every tree traversal). Under the
    cyclic walk every position owns trailing panels, so the consumer
    set is the whole grid — the row/column-restricted broadcast of a
    true 2D tile decomposition degenerates to the full tree here.
  * every host applies the broadcast factor to the trailing panels it
    owns (``StreamEngine.stash`` keeps those working states
    device-resident under the per-host HBM budget, spilling evicted
    ones through the async D2H writer), while the engine's prefetch
    thread stages the host's NEXT first-touch input — the reference's
    lookahead, reconstructed from the two existing primitives.

Bit-identity: the right-looking schedule applies, to every panel, the
same update sequence (factors 0..k-1 in order) through the SAME jitted
kernels on bitwise-equal operands as the single-device left-looking
stream, so ``shard_potrf_ooc``/``shard_geqrf_ooc`` reproduce
``potrf_ooc``/``geqrf_ooc`` results exactly — including at budget 0,
where every stash degenerates to write-through (the uncached
schedule). Pinned by tests on the single-process mesh.

Routing: the linalg/ooc.py drivers take ``grid=``/``method=`` and
arbitrate through core/methods.MethodOOC — the FROZEN
``ooc/shard_method`` default is "stream", so a cold cache keeps the
single-device path bit-identically even when a grid is supplied.

Issue order and lookahead: the step-synchronous schedule above idles
every host while panel k's broadcast completes, then again while the
owner of k+1 factors it. SLATE's lookahead (PAPER.md: critical-path
panel work overlapped with trailing updates; BLASX is the
multi-accelerator precedent) has an exact mesh-scale analogue: at step
k the owner of panel k+1 applies its OWN k-update first
(``CyclicSchedule.update_order`` — owned-next-panel-first), factors
k+1, and every host dispatches the k+1 broadcast asynchronously
(``PanelBroadcaster.broadcast_async``, a second in-flight frame)
BEFORE its remaining k-updates; the frame is completed only at step
k+1, so the collective's wall hides under the update sweep. With
broadcasts in flight the order in which a host may issue its work is
a partial order, not a loop, so the drivers do not write one: each
hands ``_run_stream`` its closures, ``sched/policies.sharded_stream``
builds the stream as a dependency graph in which the lookahead depth
only moves the slot a panel's factor and broadcast are keyed at, and
``sched/runtime.execute`` issues whatever is ready in a deterministic
order. The depth changes only WHEN identical jitted kernels run,
never their operands — each trailing panel still receives updates
0..k-1 in ascending order through the same compiled programs — so
every depth is BITWISE equal to the synchronous schedule (pinned for
all three drivers, on one process and on the 2-process gloo mesh).
Depth rides the FROZEN ``ooc/shard_lookahead`` = 0 tunable, the
per-step broadcast wait is published as the ``shard::bcast_wait``
span + ``ooc.shard.bcast_wait_seconds`` counter so the overlap
fraction is directly attributable, and the checkpoint epoch commit
trails the deepest in-flight panel (a crash with two panels live
resumes bitwise — the in-flight panel was never claimed durable).
The elastic route (dist/elastic.py) builds the same graph once per
re-ownership segment.

Mixed-precision frames (ISSUE 12): under the ``ooc/precision`` bf16
mode (FROZEN "f32" — the cold cache keeps every schedule here
bit-identically) the owner demotes the factor frame BEFORE the tree,
so every ppermute hop carries half the bytes (``ooc.shard.bcast_
bytes`` shows exactly the halving); every host applies the lo frame
through the mixed visit kernels (linalg/ooc.py ``*_mx``) and mirrors
the PROMOTED frame into its host factor, so owner and non-owner
copies stay identical across the mesh — the whole mesh's factor is
the bf16-update one, the pod-scale reduced-precision play of the TPU
distributed-linalg paper, with the OOC solves' refinement as the
accuracy contract. The LU pivot selection, whose row indices exceed
bf16's 256-integer window, rides a byte-split PAIR of payload rows
(hi*256 + lo, both exact), keeping the one-frame-per-panel
transport.

``shard_getrf_ooc`` (ISSUE 10) closes the LU deferral that PR 7
recorded: partial pivoting's host-side row-swap fixup rewrote rows
of already-written L panels (until PR 47; its panels' rows are still
final only after the last panel's pivots) — under sharding, an
epoch-bump broadcast plus a re-stage storm per cross-panel pivot. The
unlock is CALU-style
tournament pivoting (linalg/ca.tournament_pivot_rows, the structure
"Large Scale Distributed Linear Algebra With TPUs" uses for
TPU-distributed LU): the owner finalizes panel k's pivot permutation
BEFORE the factor column is written, the factor is stored in ORIGINAL
row order with the permutation applied at visit time by a device
index gather (ooc._lu_visit_orig), and the broadcast frame carries
the panel's pivot-row selection as one extra payload row the way the
QR frame carries tau — every host rederives the identical permutation
bookkeeping from that row, no retroactive fixup, no cross-shard
invalidation. Results are BITWISE equal to the single-engine
``getrf_tntpiv_ooc`` (same kernels, same operands per (panel, step)
pair); routing is earned the same way (MethodOOC; the partial-pivot
mode never shards).
"""

from __future__ import annotations

import functools
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.tiles import ceil_div
from ..obs import events as obs_events
from ..obs import health as _health
from ..obs import ledger as _ledger
from ..obs import metrics as obs_metrics
from ..obs.events import instrument_driver
from ..parallel.mesh import ProcessGrid
from ..parallel.smap import shard_map
from ..resil import checkpoint as _ckpt
from ..resil import faults as _faults
from ..resil import guard as _guard
from ..sched.policies import sharded_stream
from ..sched.runtime import execute
from . import tree as _tree


class CyclicSchedule:
    """Static 2D-block-cyclic panel->mesh-position ownership map (one
    per driver invocation; module doc). The schedule is global
    knowledge — every process computes the same map, which is what
    makes the SPMD broadcast loop and the exact per-host prefetch
    possible without any coordination traffic."""

    def __init__(self, nt: int, grid: ProcessGrid) -> None:
        self.nt = int(nt)
        self.grid = grid
        self.p, self.q = grid.p, grid.q
        self.devs = list(grid.mesh.devices.flat)   # row-major (p, q)

    @property
    def nranks(self) -> int:
        return self.p * self.q

    def owner_coords(self, k: int) -> Tuple[int, int]:
        """Grid position owning panel k: the column-major cyclic walk
        ('p' advances fastest — GridOrder.Col, mesh.py)."""
        return k % self.p, (k // self.p) % self.q

    def owner_flat(self, k: int) -> int:
        """Index of the owner in the row-major flattened device list
        (the broadcast-tree position)."""
        r, c = self.owner_coords(k)
        return r * self.q + c

    def owner_device(self, k: int):
        return self.devs[self.owner_flat(k)]

    def owner_process(self, k: int) -> int:
        return self.owner_device(k).process_index

    def is_mine(self, k: int) -> bool:
        return self.owner_process(k) == jax.process_index()

    def my_panels(self) -> List[int]:
        """Panels THIS PROCESS stages, in factoring order — the exact
        per-host touch schedule prefetch runs on."""
        return [k for k in range(self.nt) if self.is_mine(k)]

    def update_order(self, k: int, depth: int = 0,
                     epoch: int = 0) -> List[int]:
        """Step k's trailing-update order for THIS process: the
        owned-next-panel-first query (ISSUE 11). Panels inside the
        lookahead window ``(k, k+depth]`` come first — the owner of
        panel k+1 must finish that panel's k-update before ANY host
        can see frame k+1, so its update is the mesh's critical path
        — then the remaining owned trailing panels in ascending
        order. Because the window panels ARE the smallest trailing
        indices, the sequence is IDENTICAL for every depth (the
        promoted head is a prefix of the synchronous walk) — that
        prefix property is exactly why the lookahead reordering is
        bitwise-safe and why :meth:`staged_bytes`'s walk is
        depth-invariant, and this query is where it is stated and
        tested rather than assumed. ``sched/policies.sharded_stream``
        keys the sweep's nodes in this order; the promoted panels
        are the window-∩-owned prefix. Panels below ``epoch`` are
        durable on resume and never re-updated (resil/ contract)."""
        todo = [j for j in self.my_panels() if j > k and j >= epoch]
        if depth <= 0:
            return todo
        head = [j for j in todo if j <= k + depth]
        return head + [j for j in todo if j > k + depth]

    def staged_bytes(self, heights: Dict[int, int], width: int,
                     last_width: int, itemsize: int,
                     depth: int = 0) -> int:
        """Exact bytes this process's engine stages in an
        eviction-free run: each owned panel's input once, summed by
        walking the schedule (factor touch, then the step's update
        order) and charging first touches. `heights[k]` is panel k's
        staged row count (n - k0 for the triangular stream, m for the
        full-height QR stream). ``depth`` selects the lookahead walk
        (ISSUE 11): the promotion reorders WITHIN a step but the
        first-touch set and its step assignment are unchanged, so the
        prediction is depth-invariant — asserted by test, and what
        keeps the exact-schedule assertions in ``bench.py --shard``
        green at every depth."""
        total = 0
        touched: set = set()
        for k in range(self.nt):
            walk = ([k] if self.is_mine(k) else []) \
                + self.update_order(k, depth)
            for j in walk:
                if j in touched:
                    continue
                touched.add(j)
                w = last_width if j == self.nt - 1 else width
                total += heights[j] * w * itemsize
        return total


#: compiled broadcast programs, shared ACROSS driver invocations on
#: the same mesh (Mesh is hashable): without this every stream would
#: re-trace the tree per call — the jit cache keys on the closure
#: object, which a per-instance builder would recreate. Bounded in
#: practice: one entry per (mesh, panel shape, dtype, fanin).
_BCAST_FNS: Dict[Tuple, Callable] = {}


def _bcast_fn(mesh, shape: Tuple[int, ...], dtype, fanin: int,
              size: int) -> Callable:
    key = (mesh, tuple(shape), np.dtype(dtype).str, fanin)
    fn = _BCAST_FNS.get(key)
    if fn is not None:
        return fn
    # cache-stats counter (ISSUE 11 satellite): one increment per NEW
    # compiled broadcast program. tau/pivot payload rows change the
    # shape per driver, and the lookahead's second frame buffer reuses
    # the SAME programs — a whole stream must cost <= one compile per
    # distinct payload shape regardless of depth (pinned by test, so
    # a pipeline regression cannot silently double the compile count)
    obs_metrics.inc("ooc.shard.bcast_compiles")

    def combine(xs):
        return _tree.tree_combine(
            xs, lambda vals: functools.reduce(jnp.add, vals),
            ("p", "q"), size, fanin=fanin)

    fn = jax.jit(shard_map(
        combine, mesh=mesh,
        in_specs=P(("p", "q"), *([None] * len(shape))),
        out_specs=P(), check_vma=False))
    _BCAST_FNS[key] = fn
    return fn


class _InflightFrame:
    """One dispatched-but-uncompleted broadcast — the lookahead's
    second frame buffer (module doc). Holds the replicated global
    array (the collective is already running in the backend's async
    stream), the panel index, and the dispatch timestamp the overlap
    accounting keys on."""

    __slots__ = ("out", "panel", "issued_at")

    def __init__(self, out, panel: Optional[int]) -> None:
        self.out = out
        self.panel = panel
        self.issued_at = time.perf_counter()


class PanelBroadcaster:
    """Factor-panel broadcast over the dist/tree.py combine engine:
    the owner's device holds the payload, every other mesh position
    holds exact zeros, and a log-depth add-combine replicates it
    bitwise (x + 0.0 is exact for finite x). One compiled program per
    (mesh, payload shape) — cached across invocations and counted by
    ``ooc.shard.bcast_compiles`` — so a whole stream costs at most
    one compile per distinct payload shape (full panels + the narrow
    tail) at ANY lookahead depth. Each traversal publishes its
    scheduled ppermute count to the obs comms accounting
    (tree.record_schedule), exactly like tsqr/stedc.

    ``broadcast_async`` / ``complete`` split one broadcast into
    dispatch and deferred completion (ISSUE 11): dispatch returns an
    :class:`_InflightFrame` immediately (the jitted traversal runs in
    the backend's async stream), completion blocks only when the
    frame's values are first needed — the wall it fails to hide is
    the ``shard::bcast_wait`` span / ``ooc.shard.bcast_wait_seconds``
    counter, and 1 - wait/in-flight is the overlap fraction
    ``bench.py --shard`` reports per depth. ``broadcast`` composes
    the two (the synchronous form the tail panels keep)."""

    def __init__(self, grid: ProcessGrid, fanin: int = 2) -> None:
        self.grid = grid
        self.fanin = max(int(fanin), 2)
        self.mesh = grid.mesh
        self.devs = list(grid.mesh.devices.flat)
        self.size = len(self.devs)
        self._zeros: Dict[Tuple, Any] = {}
        self.panels = 0
        self.bytes = 0
        # overlap accounting (seconds; plain attributes so the stats
        # read with obs off, like StreamEngine's)
        self.wait_seconds = 0.0
        self.inflight_seconds = 0.0
        self.ahead = 0

    def _fn(self, shape: Tuple[int, ...], dtype) -> Callable:
        return _bcast_fn(self.mesh, shape, dtype, self.fanin,
                         self.size)

    def _zero(self, dev, shape: Tuple[int, ...], dtype):
        key = (dev.id, tuple(shape), np.dtype(dtype).str)
        z = self._zeros.get(key)
        if z is None:
            z = jax.device_put(jnp.zeros((1,) + tuple(shape), dtype),
                               dev)
            self._zeros[key] = z
        return z

    def broadcast_async(self, payload, owner_flat: int,
                        shape: Tuple[int, ...], dtype,
                        panel: Optional[int] = None,
                        ahead: bool = False) -> _InflightFrame:
        """Dispatch the replication of `payload` ((shape)-shaped
        device array on the OWNER process; ignored elsewhere) from
        mesh position `owner_flat` and return the in-flight frame
        WITHOUT waiting for the collective — jit dispatch is async,
        so the traversal executes in the backend stream while the
        caller keeps issuing work. Every process must call in
        lockstep (SPMD collective); the values are realized by
        :meth:`complete`. ``ahead=True`` marks a lookahead issue (the
        ``ooc.shard.bcast_ahead`` counter the cold-route pin reads —
        the frozen depth 0 must never dispatch ahead)."""
        me = jax.process_index()
        shards = []
        for i, dev in enumerate(self.devs):
            if dev.process_index != me:
                continue
            if i == owner_flat:
                shards.append(jax.device_put(
                    jnp.reshape(payload, (1,) + tuple(shape)), dev))
            else:
                shards.append(self._zero(dev, shape, dtype))
        sharding = NamedSharding(
            self.mesh, P(("p", "q"), *([None] * len(shape))))
        garr = jax.make_array_from_single_device_arrays(
            (self.size,) + tuple(shape), sharding, shards)
        nb = int(np.dtype(dtype).itemsize) * int(np.prod(shape))
        self.panels += 1
        self.bytes += nb
        if ahead:
            self.ahead += 1

        def traverse():
            # record_schedule's resil hook IS the `ppermute` injection
            # site, so it lives inside the retried unit: an injected
            # collective fault re-runs the whole traversal (every
            # host retries in lockstep — the occurrence counters are
            # per-process deterministic). A lookahead issue makes the
            # IN-FLIGHT frame the injection site: the fault fires at
            # dispatch, one step before the frame's values are used
            self._check_faults()
            return self._fn(tuple(shape), dtype)(garr)

        def run():
            if _faults.active() is not None:
                return _guard.retry(traverse, "ppermute",
                                    op="shard_bcast", size=self.size)
            try:
                return traverse()
            except Exception as e:
                # a REAL transient collective failure (not injected)
                # takes the same bounded retry
                if not _guard.is_transient(e):
                    raise
                return _guard.retry_after_failure(
                    traverse, "ppermute", e,
                    op="shard_bcast", size=self.size)

        if obs_events.enabled():
            obs_metrics.inc("ooc.shard.bcast_panels")
            obs_metrics.inc("ooc.shard.bcast_bytes", nb)
            if ahead:
                obs_metrics.inc("ooc.shard.bcast_ahead")
            with obs_events.span("shard::bcast", cat="shard",
                                 owner=owner_flat, bytes=nb,
                                 ahead=ahead):
                out = run()
        else:
            out = run()
        return _InflightFrame(out, panel)

    def _check_faults(self) -> None:
        _tree.record_schedule("shard_bcast", self.size, self.fanin)

    def complete(self, fr: _InflightFrame):
        """Realize an in-flight frame: block until the collective's
        local shard is ready and return the replicated panel. The
        blocked wall is the per-step ``shard::bcast_wait`` span and
        the ``ooc.shard.bcast_wait_seconds`` counter; issue-to-
        completion lands in ``ooc.shard.bcast_inflight_seconds`` so
        overlap = 1 - wait/in-flight is directly attributable
        (ISSUE 11 obs satellite)."""
        arr = fr.out.addressable_data(0)[0]
        if obs_events.enabled():
            with obs_events.span("shard::bcast_wait", cat="shard",
                                 panel=fr.panel):
                wait = _tree.complete_schedule("shard_bcast", arr)
        else:
            wait = _tree.complete_schedule("shard_bcast", arr)
        inflight = time.perf_counter() - fr.issued_at
        self.wait_seconds += wait
        self.inflight_seconds += inflight
        # flight-recorder leaf: the blocked completion wall is THE
        # collective-wait phase of the step record (obs/ledger.py)
        _ledger.credit("bcast_wait", wait)
        if obs_events.enabled():
            obs_metrics.inc("ooc.shard.bcast_wait_seconds", wait)
            obs_metrics.inc("ooc.shard.bcast_inflight_seconds",
                            inflight)
        return arr

    def overlap_fraction(self) -> float:
        """Fraction of the total issue-to-completion wall the
        schedule hid behind other work (0.0 for the synchronous
        schedule, which completes every frame at its dispatch site)."""
        if self.inflight_seconds <= 0:
            return 0.0
        return max(0.0, 1.0 - self.wait_seconds
                   / self.inflight_seconds)

    def broadcast(self, payload, owner_flat: int,
                  shape: Tuple[int, ...], dtype,
                  panel: Optional[int] = None):
        """The synchronous form: dispatch + immediate completion
        (depth-0 factor steps and the m<n tail panels)."""
        return self.complete(self.broadcast_async(
            payload, owner_flat, shape, dtype, panel=panel))


def _shard_fanin(fanin: Optional[int], n: int, dtype) -> int:
    if fanin:
        return int(fanin)
    from ..tune.select import resolve
    return int(resolve("ooc", "shard_fanin", n=n, dtype=dtype))


def _shard_lookahead(lookahead: Optional[int], n: int, dtype) -> int:
    """Broadcast-pipeline depth: explicit argument > the tuned/frozen
    ``ooc/shard_lookahead`` row (core/methods.MethodOOC.lookahead;
    FROZEN 0 = the step-synchronous schedule, bit-identical)."""
    if lookahead is not None:
        return max(int(lookahead), 0)
    from ..core.methods import MethodOOC
    return MethodOOC.lookahead(n, dtype)


def _panel_bounds(k: int, w: int, n: int, kmax: int
                  ) -> Tuple[int, int, int, int]:
    """Panel k's (k0, k1, wk, wf): column window, its width, and the
    factored-column count (wf < wk only when kmax = min(m, n) falls
    inside the panel — the m<n boundary the QR/LU payload builders
    share)."""
    k0, k1 = k * w, min(k * w + w, n)
    return k0, k1, k1 - k0, min(k1, kmax) - k0


def _host_ckpt_path(path: Optional[str]) -> Optional[str]:
    """Per-host checkpoint directory under the shared `path`: hosts
    snapshot their LOCAL factor mirror independently (each writes
    every factor panel through its own engine), so two processes on
    one filesystem must not share memmaps or meta."""
    if path is None:
        return None
    return os.path.join(path, "host%d" % jax.process_index())


def _agree_epoch(grid: ProcessGrid, epoch: int) -> int:
    """Checkpoint-resume epoch agreement (resil/, ISSUE 9): hosts
    crash at different commit points, so the mesh resumes at the MIN
    committed epoch — a tree min-reduction over every device (the
    dist/tuneshare transport shape). Single-process meshes short-
    circuit (every device is this host's epoch)."""
    devs = list(grid.mesh.devices.flat)
    if len({d.process_index for d in devs}) == 1:
        return int(epoch)
    from ..parallel.collectives import tree_allreduce
    me = jax.process_index()
    shards = [jax.device_put(jnp.asarray([epoch], jnp.int32), d)
              for d in devs if d.process_index == me]
    sharding = NamedSharding(grid.mesh, P(("p", "q")))
    garr = jax.make_array_from_single_device_arrays(
        (len(devs),), sharding, shards)
    out = tree_allreduce(grid, garr, op=jnp.minimum)
    return int(np.asarray(out.addressable_data(0))[0])


#: counters each per-step obs record reports as deltas
_STEP_OBS_KEYS = ("ooc.h2d_bytes", "ooc.d2h_bytes",
                  "ooc.shard.bcast_panels", "ooc.shard.bcast_bytes")


def _step_obs_fn(op: str) -> Callable[[int], None]:
    """Per-step incremental obs publisher (the streaming-obs
    satellite, ISSUE 10): after each panel step the driver publishes
    that step's DELTA of the staging/broadcast counters as one
    ``shard::step_obs`` instant, so a long sharded run's progress is
    visible on the event bus while it runs instead of only in the
    exit snapshot — and multi-process workers can relay the same
    increments over the result handshake
    (testing/multiproc.emit_obs_delta). The baseline lives in this
    closure (per driver invocation) and is seeded from the counters
    AT CREATION, so concurrent drivers never steal each other's
    deltas and step 0 reports only this driver's work — not whatever
    earlier drivers accumulated since the last metrics.reset(). Free
    when obs is disabled."""
    seed = obs_metrics.snapshot()["counters"]
    prev: Dict[str, float] = {key: seed.get(key, 0)
                              for key in _STEP_OBS_KEYS}

    def publish(k: int) -> None:
        if not obs_events.enabled():
            return
        cur = obs_metrics.snapshot()["counters"]
        delta = {key.rsplit(".", 1)[-1]:
                 cur.get(key, 0) - prev.get(key, 0)
                 for key in _STEP_OBS_KEYS}
        prev.update({key: cur.get(key, 0) for key in _STEP_OBS_KEYS})
        obs_events.instant("shard::step_obs", cat="shard", op=op,
                           step=k, **delta)

    return publish


class _ShardState:
    """Per-host trailing-panel working set: first touch stages the
    input through the engine (exact, schedule-known prefetch), later
    touches hit the stash or re-stage the spilled state from the
    host-side scratch (`ws`, allocated lazily — only spilled panels
    ever cost host scratch).

    ``upto`` is the elastic route's segment bookkeeping
    (dist/elastic.py run_elastic): the next update step each owned
    panel has NOT yet absorbed, set at a segment boundary so the next
    segment's graph prunes the updates already applied. Within one
    graph a record's consumers are explicit edges and nothing writes
    it."""

    def __init__(self, eng, loader: Callable[[int], Callable],
                 scratch: Callable[[int], Tuple[int, ...]],
                 dtype) -> None:
        self.eng = eng
        self._loader = loader          # k -> input loader callable
        self._scratch = scratch        # k -> spill buffer shape
        self.dtype = dtype
        self.ws: Dict[int, np.ndarray] = {}
        self.staged: set = set()
        #: panel -> next update step it still needs
        self.upto: Dict[int, int] = {}

    def applied_through(self, j: int) -> int:
        return self.upto.get(j, 0)

    def spill_view(self, k: int) -> Callable[[], np.ndarray]:
        def view():
            if k not in self.ws:
                self.ws[k] = np.empty(self._scratch(k), self.dtype)
            return self.ws[k]
        return view

    def take(self, k: int):
        if k not in self.staged:
            self.staged.add(k)
            return self.eng.fetch("S", k, self._loader(k), cache=False)
        return self.eng.fetch("S", k, lambda: self.ws[k])

    def prefetch_panel(self, k: Optional[int]) -> None:
        """Exact lookahead by panel index: stage k's first-touch input
        unless it is already staged (re-stages of spilled states
        contend with their own spill writes and stay synchronous).
        The graph binds each sweep's target when it is built
        (sched/policies.py)."""
        if k is not None and k not in self.staged:
            self.eng.prefetch("S", k, self._loader(k), cache=False)

    def stash(self, k: int, arr) -> None:
        self.eng.stash("S", k, arr, self.spill_view(k))

    def discard(self, k: int) -> None:
        self.eng.discard("S", k)
        self.ws.pop(k, None)


def _publish_overlap(op: str, bc: PanelBroadcaster,
                     depth: int) -> None:
    """Driver-exit overlap record (ISSUE 11 obs satellite): the
    broadcast-wait wall vs the in-flight wall and their fraction, so
    bench/report attribute the lookahead win without re-deriving it
    from spans."""
    if not obs_events.enabled():
        return
    obs_metrics.observe("ooc.shard.bcast_overlap_fraction",
                        bc.overlap_fraction())
    obs_events.instant("shard::overlap", cat="shard", op=op,
                       depth=depth, ahead=bc.ahead,
                       wait_s=round(bc.wait_seconds, 6),
                       inflight_s=round(bc.inflight_seconds, 6),
                       overlap=round(bc.overlap_fraction(), 4))


def _run_stream(op: str, *, sched, bc, st, depth, epoch,
                factor_panels, tail_panels, payload_shape,
                make_payload, complete, replay, apply, tail_step,
                led, ck, eng, step_obs, nt, elastic=None) -> None:
    """The issue loop of all three sharded drivers: the stream is
    built once as a dependency graph (``sched/policies.
    sharded_stream``) and ``sched/runtime.execute`` issues its ready
    nodes. Each driver supplies five closures over its own kernels
    and bookkeeping:

      * ``payload_shape(k)`` -> (shape, dtype) of panel k's broadcast
        frame (potrf: (n, wk); geqrf/getrf: (m+1, wk) — the extra
        payload row);
      * ``make_payload(k, S)`` -> the owner-side device payload from
        the fully-updated panel state S (factor kernels +
        guard.check_panel live here);
      * ``complete(k, replicated)`` -> the step's update record
        (host-side bookkeeping — taus/pivot materialization, the
        local factor-mirror write — runs HERE, exactly once per
        panel, in strictly ascending panel order);
      * ``replay(k)`` -> the update record from the durable per-host
        mirror (resume panels below the agreed epoch — no factor
        work, no broadcast);
      * ``apply(S, rec, j)`` -> panel j's state after absorbing the
        record's update (the SAME jitted visit kernel at every
        depth);

    plus ``tail_step(k)`` — the m<n tail-panel body (None for potrf,
    whose every panel factors).

    ``elastic`` (ISSUE 19): an :class:`~.elastic.ElasticController`
    routes the stream through the segmented re-ownership loop
    (dist/elastic.py run_elastic — one such graph per remap segment,
    ownership re-derived from measured throughput at each
    boundary)."""
    if elastic is not None:
        from . import elastic as _elastic
        _elastic.run_elastic(
            elastic, op=op, bc=bc, st=st, depth=depth, epoch=epoch,
            factor_panels=factor_panels, tail_panels=tail_panels,
            payload_shape=payload_shape, make_payload=make_payload,
            complete=complete, replay=replay, apply=apply,
            tail_step=tail_step, led=led, ck=ck, eng=eng,
            step_obs=step_obs, nt=nt)
        return
    last = factor_panels[-1] if len(factor_panels) else -1
    g = sharded_stream(
        op, sched=sched, bc=bc, st=st, depth=depth, epoch=epoch,
        factor_panels=factor_panels, tail_panels=tail_panels,
        payload_shape=payload_shape, make_payload=make_payload,
        complete=complete, replay=replay, apply=apply,
        tail=tail_step)

    def _begin(k):
        if led is not None:
            led.begin(k, owner=sched.owner_process(k), epoch=epoch)

    def _end(k):
        if k <= last:
            step_obs(k)
        if ck is not None and k >= epoch and ck.due(k):
            eng.wait_writes()   # every panel <= k is durable;
            ck.commit(k + 1)    # the in-flight panel is NOT
        if led is not None:
            led.commit()

    execute(g, op=op, nt=nt, begin_step=_begin, end_step=_end)
    # deep lookahead keys every node below slot nt-1, so the trailing
    # slots never open and their due() commits never fire from _end:
    # land the final complete checkpoint explicitly
    if ck is not None and ck.epoch < nt:
        eng.wait_writes()
        ck.commit(nt)


@instrument_driver("shard_potrf_ooc")
def shard_potrf_ooc(a: np.ndarray, grid: ProcessGrid,
                    panel_cols: Optional[int] = None,
                    cache_budget_bytes=None,
                    fanin: Optional[int] = None,
                    lookahead: Optional[int] = None,
                    ckpt_path: Optional[str] = None,
                    ckpt_every: Optional[int] = None,
                    precision=None,
                    ownership=None) -> np.ndarray:
    """Sharded out-of-core lower Cholesky (module doc): panels owned
    2D-block-cyclically, each host staging only its shard, factor
    panels broadcast over the tree. Returns the full host-resident
    lower factor ON EVERY PROCESS (each broadcast panel is written
    back locally), bitwise equal to ``potrf_ooc``'s.

    ``lookahead`` (ISSUE 11): the broadcast-pipeline depth (explicit
    argument > the FROZEN ``ooc/shard_lookahead`` = 0). Depth 0 is
    the step-synchronous schedule; depth >= 1 overlaps each step's
    trailing updates with the NEXT panel's in-flight broadcast
    (module doc) — bitwise equal at every depth, pinned by tests.

    ``ckpt_path``/``ckpt_every`` (resil/, ISSUE 9): each host keeps a
    durable per-host mirror of the factor (resil/checkpoint.py memmap
    under ``ckpt_path/host<i>``). On resume the mesh agrees on the
    MIN committed epoch (:func:`_agree_epoch`); panels below it are
    replayed from the durable local mirror — no factor work, no
    broadcast — while each host's trailing panels catch up through
    the SAME jitted update kernel on bitwise-equal operands, so the
    resumed factor is BITWISE the uninterrupted one (pinned by
    tests, including a crash with two panels in flight — the commit
    epoch always trails the deepest in-flight panel). FROZEN default
    0 = off, bit-identical to the pre-resil driver.

    ``precision`` (ISSUE 12): the mixed-precision mode, resolved
    explicit > tuned ``ooc/precision`` > FROZEN "f32" (the cold
    cache keeps this full-precision schedule bit-identically).
    Under "bf16" the factor panel is demoted BEFORE broadcast — the
    ppermute tree carries half the bytes per frame (the
    ``ooc.shard.bcast_bytes`` counter shows exactly the halving) —
    every host applies the bf16 frame with the mixed update kernel,
    and the host factor mirror holds the PROMOTED frame, so every
    process (owner included) derives its copy from the same
    broadcast value: the mesh-wide factor stays identical across
    hosts, at bf16-update accuracy. Resume replay demotes the
    promoted mirror back (an exact roundtrip) so a resumed stream
    applies bitwise the frames the uninterrupted one did.

    ``ownership`` (ISSUE 19): ``"static"`` (FROZEN ``mesh/ownership``
    default — the pure cyclic map) or ``"elastic"`` (throughput-
    driven re-ownership, dist/elastic.py — bitwise vs static; with
    uniform throughput the remapper never fires)."""
    from ..linalg import stream
    from ..linalg.ooc import (_panel_apply, _panel_apply_mx,
                              _panel_cols, _panel_factor,
                              _precision_meta, _resolve_precision)
    from .elastic import ElasticController, _resolve_ownership
    a = np.asarray(a)
    n = a.shape[0]
    w = min(_panel_cols(panel_cols, n, a.dtype), n)
    nt = ceil_div(n, w)
    lo = _resolve_precision(precision, n, a.dtype)
    depth = _shard_lookahead(lookahead, n, a.dtype)
    ctrl = ElasticController("shard_potrf_ooc", grid, nt,
                             n=n, dtype=a.dtype) \
        if _resolve_ownership(ownership, n, a.dtype) else None
    sched = ctrl.sched if ctrl is not None \
        else CyclicSchedule(nt, grid)
    bc = PanelBroadcaster(grid, _shard_fanin(fanin, n, a.dtype))
    ck = _ckpt.maybe_checkpointer(
        _host_ckpt_path(ckpt_path), "shard_potrf_ooc", a, w, nt,
        every=ckpt_every,
        extra_meta={"precision": _precision_meta(lo)})
    # np.zeros maps untouched pages (linalg/ooc.py potrf_ooc)
    out = ck.factor if ck is not None else np.zeros(a.shape, a.dtype)
    epoch = _agree_epoch(grid, ck.epoch) if ck is not None else 0
    local_dev = jax.local_devices()[0]
    eng = stream.engine_for(n, w, a.dtype,
                            budget_bytes=cache_budget_bytes,
                            device=local_dev, extra_pins=depth,
                            resident_dtype=lo)
    mine = sched.my_panels()
    if obs_events.enabled():
        obs_events.instant("shard::schedule", cat="shard", op="potrf",
                           nt=nt, ranks=sched.nranks, mine=len(mine),
                           lookahead=depth, resume_epoch=epoch,
                           precision=_precision_meta(lo))

    def loader(k):
        k0, k1 = k * w, min(k * w + w, n)
        return lambda: a[k0:, k0:k1]

    st = _ShardState(eng, loader,
                     lambda k: (n - k * w, min(w, n - k * w)),
                     a.dtype)
    step_obs = _step_obs_fn("potrf")

    def payload_shape(k):
        return (n, min(w, n - k * w)), \
            (a.dtype if lo is None else lo)

    def make_payload(k, S):
        k0 = k * w
        Lk = _panel_factor(S, min(w, n - k0))
        _guard.check_panel("shard_potrf_ooc", k, Lk, ref=S)
        if lo is not None:
            # demote BEFORE broadcast: the tree carries half the
            # bytes, and every host (owner included) derives both
            # its updates and its factor mirror from the same lo
            # frame
            Lk = stream.demote_dev(Lk, lo)
        return stream._embed_rows(Lk, k0, n=n)

    def complete(k, frame):
        # every host mirrors the factor panel into its own copy
        # (promoted back under the mixed mode — the host factor
        # keeps the compute dtype)
        k0, k1 = k * w, min(k * w + w, n)
        col = frame if lo is None \
            else stream.promote_dev(frame, a.dtype)
        eng.write("L", k, stream._suffix_rows(col, k0, rows=n - k0),
                  out[k0:, k0:k1])
        return frame

    def replay(k):
        # resume: panel k's factor is durable in the local mirror —
        # skip factor/broadcast/write and just catch the trailing
        # owned panels up (module doc). Mixed: the mirror holds the
        # promoted frame; demoting it back is an exact roundtrip
        k0, k1 = k * w, min(k * w + w, n)
        if lo is None:
            return stream._h2d(out[:, k0:k1])
        return stream._h2d(stream.demote_host(out[:, k0:k1], lo))

    def apply(S_j, frame, j):
        j0 = j * w
        Lr = stream._suffix_rows(frame, j0, rows=n - j0)
        if lo is None:
            return _panel_apply(S_j, Lr, min(w, n - j0))
        return _panel_apply_mx(S_j, Lr, min(w, n - j0))

    led = _ledger.recorder("shard_potrf_ooc", nt=nt,
                           spill_dir=_host_ckpt_path(ckpt_path))
    try:
        _run_stream("shard_potrf_ooc", sched=sched, bc=bc, st=st,
                    depth=depth, epoch=epoch,
                    factor_panels=list(range(nt)), tail_panels=[],
                    payload_shape=payload_shape,
                    make_payload=make_payload, complete=complete,
                    replay=replay, apply=apply, tail_step=None,
                    led=led, ck=ck, eng=eng, step_obs=step_obs,
                    nt=nt, elastic=ctrl)
        _health.heartbeat("shard_potrf_ooc", nt, nt)   # completion
        if led is not None:
            led.begin(nt, epoch=epoch, drain=True)       # final drain record
        eng.wait_writes()
    finally:
        eng.finish()
        if led is not None:
            led.close()
    _publish_overlap("potrf", bc, depth)
    return out


@instrument_driver("shard_geqrf_ooc")
def shard_geqrf_ooc(a: np.ndarray, grid: ProcessGrid,
                    panel_cols: Optional[int] = None,
                    incore_ib: int = 128,
                    cache_budget_bytes=None,
                    fanin: Optional[int] = None,
                    lookahead: Optional[int] = None,
                    ckpt_path: Optional[str] = None,
                    ckpt_every: Optional[int] = None,
                    precision=None,
                    ownership=None):
    """Sharded out-of-core Householder QR: same ownership walk,
    broadcast tree, and lookahead pipeline as shard_potrf_ooc,
    full-height panel states, the broadcast payload carrying the
    factored column frame PLUS one extra row holding the panel's taus
    (one tree traversal per step covers both). Returns (QR_packed,
    taus) on every process, bitwise equal to ``geqrf_ooc``'s packed
    contract at every ``lookahead`` depth.

    ``ckpt_path``/``ckpt_every``: per-host durable factor + taus
    mirrors with the same min-epoch agreement and durable-mirror
    replay as shard_potrf_ooc (resil/, ISSUE 9).

    ``precision`` "bf16" (ISSUE 12): the broadcast frame — packed
    column AND its tau row — is demoted before the tree (half the
    payload bytes); hosts apply the compact-WY block with the mixed
    kernel and mirror the promoted frame, so the packed factor and
    taus are identical across the mesh at bf16-update accuracy.

    ``ownership`` (ISSUE 19): "static" | "elastic" — the
    shard_potrf_ooc contract."""
    from ..linalg import stream
    from ..linalg.ooc import (_panel_cols, _precision_meta,
                              _qr_apply_fresh, _qr_panel_factor,
                              _qr_visit, _qr_visit_mx,
                              _resolve_precision)
    from .elastic import ElasticController, _resolve_ownership
    a = np.asarray(a)
    m, n = a.shape
    kmax = min(m, n)
    w = min(_panel_cols(panel_cols, n, a.dtype), n)
    nt = ceil_div(n, w)
    lo = _resolve_precision(precision, n, a.dtype)
    depth = _shard_lookahead(lookahead, n, a.dtype)
    ctrl = ElasticController("shard_geqrf_ooc", grid, nt,
                             n=n, dtype=a.dtype) \
        if _resolve_ownership(ownership, n, a.dtype) else None
    sched = ctrl.sched if ctrl is not None \
        else CyclicSchedule(nt, grid)
    bc = PanelBroadcaster(grid, _shard_fanin(fanin, n, a.dtype))
    ck = _ckpt.maybe_checkpointer(
        _host_ckpt_path(ckpt_path), "shard_geqrf_ooc", a, w, nt,
        every=ckpt_every, extra_arrays={"taus": ((kmax,), a.dtype)},
        extra_meta={"precision": _precision_meta(lo)})
    if ck is not None:
        out, taus = ck.factor, ck.array("taus")
        epoch = _agree_epoch(grid, ck.epoch)
    else:
        out = np.empty_like(a)
        taus = np.zeros((kmax,), a.dtype)
        epoch = 0
    local_dev = jax.local_devices()[0]
    eng = stream.engine_for(max(m, n), w, a.dtype,
                            budget_bytes=cache_budget_bytes,
                            device=local_dev, extra_pins=depth,
                            resident_dtype=lo)
    mine = sched.my_panels()
    if obs_events.enabled():
        obs_events.instant("shard::schedule", cat="shard", op="geqrf",
                           nt=nt, ranks=sched.nranks, mine=len(mine),
                           lookahead=depth,
                           precision=_precision_meta(lo))

    def loader(k):
        k0, k1 = k * w, min(k * w + w, n)
        return lambda: a[:, k0:k1]

    st = _ShardState(eng, loader,
                     lambda k: (m, min(w, n - k * w)), a.dtype)
    step_obs = _step_obs_fn("geqrf")
    factor_panels = [k for k in range(nt) if k * w < kmax]
    tail_panels = [k for k in range(nt) if k * w >= kmax]

    def bounds(k):
        return _panel_bounds(k, w, n, kmax)

    def payload_shape(k):
        _k0, _k1, wk, _wf = bounds(k)
        return (m + 1, wk), (a.dtype if lo is None else lo)

    def make_payload(k, S):
        k0, _k1, wk, wf = bounds(k)
        packed, ptau = _qr_panel_factor(S[:, :wf], k0, incore_ib)
        _guard.check_panel("shard_geqrf_ooc", k, packed[:m - k0],
                           ref=S)
        low = packed[:m - k0]
        if wf < wk:
            # kmax falls inside this panel (m < n): the tail columns
            # are pure R rows from the fresh apply — the same
            # composition geqrf_ooc writes piecewise
            rest = _qr_apply_fresh(S[k0:, wf:], low, ptau)
            low = jnp.concatenate([low, rest], axis=1)
        col = jnp.concatenate([S[:k0], low], axis=0) if k0 > 0 \
            else low
        tau_row = jnp.zeros((1, wk), a.dtype)
        tau_row = tau_row.at[0, :wf].set(ptau[:wf])
        payload = jnp.concatenate([col, tau_row], axis=0)
        if lo is not None:
            # one demotion covers column AND tau row — the whole
            # frame rides the tree at half the bytes
            payload = stream.demote_dev(payload, lo)
        return payload

    def complete(k, payload):
        k0, k1, _wk, wf = bounds(k)
        if lo is None:
            col = payload[:m]
            taus[k0:k0 + wf] = np.asarray(payload[m, :wf])
            eng.write("QR", k, col, out[:, k0:k1])
            return col[:, :wf], payload[m, :wf], k0
        colf = stream.promote_dev(payload, a.dtype)
        taus[k0:k0 + wf] = np.asarray(colf[m, :wf])
        eng.write("QR", k, colf[:m], out[:, k0:k1])
        # the update record keeps the LO column (the mixed visit's
        # operand) plus the tau row widened to the compute dtype for
        # the kernel's f32 T algebra. The taus ARE bf16-rounded (the
        # whole frame demotes once) — the same error class as the V
        # columns riding beside them, i.e. the mode's documented
        # bf16-update-grade accuracy, NOT a restoration of full-
        # precision taus
        return payload[:m, :wf], colf[m, :wf], k0

    def replay(k):
        # resume replay from the durable per-host mirror (factor
        # column + taus hold the same device bytes the uninterrupted
        # run broadcast; mixed: demoting the promoted mirror is an
        # exact roundtrip)
        k0, k1, _wk, wf = bounds(k)
        col = stream._h2d(out[:, k0:k1]) if lo is None \
            else stream._h2d(stream.demote_host(out[:, k0:k1], lo))
        return col[:, :wf], stream._h2d(taus[k0:k0 + wf]), k0

    def apply(S_j, rec, j):
        Pk, tk, k0 = rec
        if lo is None:
            return _qr_visit(S_j, Pk, tk, k0)
        return _qr_visit_mx(S_j, Pk, tk, k0)

    def tail_step(k):
        # all updates applied: the state IS the final U block — one
        # broadcast replicates it so every host's factor is complete.
        # Ownership is read LIVE (a remap may have re-owned the tail
        # panel since construction — dist/elastic.py)
        s = ctrl.sched if ctrl is not None else sched
        k0, k1 = k * w, min(k * w + w, n)
        frame = st.take(k) if s.is_mine(k) else None
        if frame is not None:
            st.discard(k)
        frame = bc.broadcast(frame, s.owner_flat(k),
                             (m, k1 - k0), a.dtype, panel=k)
        eng.write("QR", k, frame, out[:, k0:k1])

    led = _ledger.recorder("shard_geqrf_ooc", nt=nt,
                           spill_dir=_host_ckpt_path(ckpt_path))
    try:
        _run_stream("shard_geqrf_ooc", sched=sched, bc=bc, st=st,
                    depth=depth, epoch=epoch,
                    factor_panels=factor_panels,
                    tail_panels=tail_panels,
                    payload_shape=payload_shape,
                    make_payload=make_payload, complete=complete,
                    replay=replay, apply=apply, tail_step=tail_step,
                    led=led, ck=ck, eng=eng, step_obs=step_obs,
                    nt=nt, elastic=ctrl)
        _health.heartbeat("shard_geqrf_ooc", nt, nt)   # completion
        if led is not None:
            led.begin(nt, epoch=epoch, drain=True)       # final drain record
        eng.wait_writes()
    finally:
        eng.finish()
        if led is not None:
            led.close()
    _publish_overlap("geqrf", bc, depth)
    return out, taus


@instrument_driver("shard_getrf_ooc")
def shard_getrf_ooc(a: np.ndarray, grid: ProcessGrid,
                    panel_cols: Optional[int] = None,
                    incore_nb: int = 1024,
                    cache_budget_bytes=None,
                    fanin: Optional[int] = None,
                    lookahead: Optional[int] = None,
                    chunk: Optional[int] = None,
                    ckpt_path: Optional[str] = None,
                    ckpt_every: Optional[int] = None,
                    precision=None,
                    ownership=None):
    """Sharded out-of-core tournament-pivot LU (module doc — the PR 7
    deferral, closed): same ownership walk and broadcast tree as
    shard_potrf_ooc, full-height panel states kept in ORIGINAL row
    order, the owner of panel k finalizing its pivot permutation via
    the CALU tournament BEFORE the factor column is written. The
    broadcast payload is the (m, wk) original-order factor column
    plus ONE extra row carrying the panel's live-relative pivot-row
    selection (encoded in the panel dtype the way the QR frame
    carries tau — exact for row counts below the dtype's integer
    window, 2^24 for f32); every host rederives (ipiv, permutation)
    from that row with the same host simulation
    (lu.tnt_swaps_host), so the bookkeeping is identical across the
    mesh with no extra coordination traffic. Returns (LU_packed,
    ipiv) in getrf_ooc's LAPACK packed contract ON EVERY PROCESS,
    BITWISE equal to the single-engine ``getrf_tntpiv_ooc`` — the
    trailing updates run the SAME jitted ``_lu_visit_orig`` kernel on
    bitwise-equal operands in the same per-panel order, and the
    factor columns never change after their step (no fixup, no
    cross-shard invalidation). Pinned by tests incl. a real
    2-process gloo mesh.

    ``ckpt_path``/``ckpt_every``: per-host durable mirrors of the
    original-order factor, ipiv, and the per-panel permutation
    snapshots (the "per-host pivot vectors" of the durable epoch),
    with the same min-epoch agreement and durable-mirror replay as
    shard_potrf_ooc; the meta records ``lu_pivot="tournament"`` so a
    mode-mismatched resume starts fresh (resil/checkpoint.py).

    ``precision`` "bf16" (ISSUE 12): the factor column demotes
    before the tree and the pivot-row selection rides TWO extra lo
    rows instead of one — bf16's exact-integer window is only 256,
    so the selection is split byte-wise (``hi*256 + lo``, both
    halves < 256 = exact in bf16), widening the window to 2^16 rows;
    hosts decode the same two rows, so the bookkeeping stays
    mesh-identical. Updates run the mixed gather-visit kernel and
    the original-order store mirrors the promoted column.

    ``ownership`` (ISSUE 19): "static" | "elastic" — the
    shard_potrf_ooc contract."""
    from ..core.exceptions import slate_assert
    from ..linalg import stream
    from . import elastic as _elastic_mod
    from ..linalg.ca import fix_degenerate_selection
    from ..linalg.lu import tnt_swaps_host
    from ..linalg.ooc import (_lu_visit_orig, _lu_visit_orig_mx,
                              _panel_cols, _precision_meta,
                              _resolve_precision, _tnt_factor,
                              _tnt_select, _tnt_tail_cols,
                              _finalize_lapack_order)
    a = np.asarray(a)
    m, n = a.shape
    lo = _resolve_precision(precision, n, a.dtype)
    # the pivot payload row(s) ride the FRAME dtype: row indices must
    # sit inside its exact-integer window or np.rint decodes WRONG
    # rows silently — make it a loud error instead. The mixed mode's
    # byte-split pair of lo rows has a 2^16 window (two exact bytes)
    window = (1 << 16) if lo is not None \
        else (1 << (np.finfo(a.dtype).nmant + 1))
    slate_assert(
        m <= window,
        "shard_getrf_ooc encodes pivot rows in the %s payload row%s; "
        "m=%d exceeds the exact-integer window %d — use a wider "
        "dtype or the single-engine getrf_tntpiv_ooc"
        % (np.dtype(a.dtype).name if lo is None
           else np.dtype(lo).name,
           "" if lo is None else " pair", m, window))
    kmax = min(m, n)
    w = min(_panel_cols(panel_cols, n, a.dtype), n)
    nt = ceil_div(n, w)
    nf = ceil_div(kmax, w)
    depth = _shard_lookahead(lookahead, n, a.dtype)
    ctrl = _elastic_mod.ElasticController("shard_getrf_ooc", grid,
                                          nt, n=n, dtype=a.dtype) \
        if _elastic_mod._resolve_ownership(ownership, n, a.dtype) \
        else None
    sched = ctrl.sched if ctrl is not None \
        else CyclicSchedule(nt, grid)
    bc = PanelBroadcaster(grid, _shard_fanin(fanin, n, a.dtype))
    ck = _ckpt.maybe_checkpointer(
        _host_ckpt_path(ckpt_path), "shard_getrf_ooc", a, w, nt,
        every=ckpt_every,
        extra_arrays={"ipiv": ((kmax,), np.int64),
                      "perms": ((nf, m), np.int64)},
        extra_meta={"lu_pivot": "tournament",
                    "precision": _precision_meta(lo)})
    if ck is not None:
        stored, ipiv = ck.factor, ck.array("ipiv")
        perms = ck.array("perms")
        epoch = _agree_epoch(grid, ck.epoch)
    else:
        stored = np.empty_like(a)
        ipiv = np.empty((kmax,), np.int64)
        perms = np.empty((nf, m), np.int64)
        epoch = 0
    perm = perms[min(epoch, nf) - 1].copy() if min(epoch, nf) > 0 \
        else np.arange(m)
    # high-water of the panel whose permutation `perm` currently
    # holds: completes advance it; replays only move it FORWARD (a
    # segmented elastic run replays old steps for catch-up panels
    # AFTER later completes already advanced perm — regressing it
    # would feed make_payload a stale permutation)
    perm_step = [min(epoch, nf) - 1]
    local_dev = jax.local_devices()[0]
    eng = stream.engine_for(max(m, n), w, a.dtype,
                            budget_bytes=cache_budget_bytes,
                            device=local_dev, extra_pins=depth,
                            resident_dtype=lo)
    mine = sched.my_panels()
    if obs_events.enabled():
        obs_events.instant("shard::schedule", cat="shard", op="getrf",
                           nt=nt, ranks=sched.nranks, mine=len(mine),
                           lookahead=depth, resume_epoch=epoch,
                           precision=_precision_meta(lo))

    def loader(k):
        k0, k1 = k * w, min(k * w + w, n)
        return lambda: a[:, k0:k1]

    st = _ShardState(eng, loader,
                     lambda k: (m, min(w, n - k * w)), a.dtype)
    step_obs = _step_obs_fn("getrf")
    factor_panels = [k for k in range(nt) if k * w < kmax]
    tail_panels = [k for k in range(nt) if k * w >= kmax]

    def bounds(k):
        return _panel_bounds(k, w, n, kmax)

    def payload_shape(k):
        _k0, _k1, wk, _wf = bounds(k)
        if lo is None:
            return (m + 1, wk), a.dtype
        return (m + 2, wk), lo

    def make_payload(k, S):
        # the owner's tournament runs against the CURRENT `perm`,
        # which the strictly ascending completion order has advanced
        # through frame k-1 by the time panel k is issued —
        # lookahead or not, the same host simulation on the same
        # values
        k0, _k1, wk, wf = bounds(k)
        live = m - k0
        idx = np.concatenate([perm[k0:], perm[:k0]])
        sel = _tnt_select(S, jnp.asarray(idx), live, wf, chunk=chunk)
        sel = fix_degenerate_selection(np.asarray(sel), live, wf)
        _piv, lperm = tnt_swaps_host(sel, live)
        new_live = perm[k0:][lperm]
        idx2 = np.concatenate([new_live, perm[:k0]])
        col, packed = _tnt_factor(S, jnp.asarray(idx2), live, wf,
                                  min(int(incore_nb), max(wf, 1)))
        _guard.check_panel("shard_getrf_ooc", k, col, ref=S)
        if wf < wk:
            # kmax inside this panel (m < n): the pure-U tail
            # columns join the broadcast column
            tail = _tnt_tail_cols(S, packed, new_live, wf)
            colfull = jnp.concatenate([col, tail], axis=1)
        else:
            colfull = col
        if lo is None:
            sel_row = jnp.zeros((1, wk), a.dtype)
            sel_row = sel_row.at[0, :wf].set(
                jnp.asarray(sel).astype(a.dtype))
            return jnp.concatenate([colfull, sel_row], axis=0)
        # mixed frame: demoted column + the byte-split selection
        # pair (docstring — bf16 represents 0..255 exactly)
        sel = np.asarray(sel, dtype=np.int64)
        rows = np.zeros((2, wk), dtype=lo)
        rows[0, :wf] = (sel // 256).astype(lo)
        rows[1, :wf] = (sel % 256).astype(lo)
        return jnp.concatenate(
            [stream.demote_dev(colfull, lo), jnp.asarray(rows)],
            axis=0)

    def complete(k, payload):
        k0, k1, _wk, wf = bounds(k)
        live = m - k0
        if lo is None:
            colfull = payload[:m]
            sel = np.rint(
                np.asarray(payload[m, :wf]).real).astype(np.int64)
        else:
            colfull = stream.promote_dev(payload[:m], a.dtype)
            srows = np.asarray(payload[m:m + 2, :wf]) \
                .astype(np.float32)
            sel = (np.rint(srows[0]) * 256
                   + np.rint(srows[1])).astype(np.int64)
        # EVERY host (owner included) rederives the pivot
        # bookkeeping from the broadcast selection — one
        # deterministic function of one broadcast value
        piv_rel, lperm = tnt_swaps_host(sel, live)
        perm[k0:] = perm[k0:][lperm]
        ipiv[k0:k0 + wf] = k0 + piv_rel
        perms[k] = perm
        perm_step[0] = k
        eng.write("LU", k, colfull, stored[:, k0:k1])
        # the update record keeps the LO column under the mixed mode
        # (the visit kernel's operand — the promoted copy only feeds
        # the host mirror)
        Pk = colfull[:, :wf] if lo is None else payload[:m, :wf]
        return {"Pk": Pk, "k": k, "k0": k0, "g": None}

    def replay(k):
        # resume replay: factor column, ipiv, and permutation
        # snapshot are durable in the per-host mirror — skip
        # select/factor/broadcast and catch the trailing owned
        # panels up from the mirror (module doc; mixed demote is an
        # exact roundtrip of the promoted mirror)
        k0, k1, _wk, wf = bounds(k)
        colfull = stream._h2d(stored[:, k0:k1]) if lo is None \
            else stream._h2d(stream.demote_host(stored[:, k0:k1],
                                                lo))
        if k > perm_step[0]:
            perm[:] = perms[k]
            perm_step[0] = k
        return {"Pk": colfull[:, :wf], "k": k, "k0": k0, "g": None}

    def apply(S_j, rec, j):
        if rec["g"] is None:
            # lazy: no owned trailing panels -> no index upload (the
            # perms[k] row is this step's immutable snapshot)
            rec["g"] = jnp.asarray(perms[rec["k"]].astype(np.int32))
        if lo is None:
            return _lu_visit_orig(S_j, rec["Pk"], rec["g"],
                                  rec["k0"])
        return _lu_visit_orig_mx(S_j, rec["Pk"], rec["g"],
                                 rec["k0"])

    def tail_step(k):
        # all updates applied: the original-order state IS the final
        # U block — one broadcast replicates it so every host's
        # factor is complete. Ownership read LIVE (a remap may have
        # re-owned the tail panel — dist/elastic.py)
        s = ctrl.sched if ctrl is not None else sched
        k0, k1 = k * w, min(k * w + w, n)
        frame = st.take(k) if s.is_mine(k) else None
        if frame is not None:
            st.discard(k)
        frame = bc.broadcast(frame, s.owner_flat(k),
                             (m, k1 - k0), a.dtype, panel=k)
        eng.write("LU", k, frame, stored[:, k0:k1])

    led = _ledger.recorder("shard_getrf_ooc", nt=nt,
                           spill_dir=_host_ckpt_path(ckpt_path))
    try:
        _run_stream("shard_getrf_ooc", sched=sched, bc=bc, st=st,
                    depth=depth, epoch=epoch,
                    factor_panels=factor_panels,
                    tail_panels=tail_panels,
                    payload_shape=payload_shape,
                    make_payload=make_payload, complete=complete,
                    replay=replay, apply=apply, tail_step=tail_step,
                    led=led, ck=ck, eng=eng, step_obs=step_obs,
                    nt=nt, elastic=ctrl)
        _health.heartbeat("shard_getrf_ooc", nt, nt)   # completion
        if led is not None:
            led.begin(nt, epoch=epoch, drain=True)       # final drain record
        eng.wait_writes()
    finally:
        eng.finish()
        if led is not None:
            led.close()
    _publish_overlap("getrf", bc, depth)
    if ck is not None:
        out = _finalize_lapack_order(stored, perm, w,
                                     out=np.empty_like(stored))
        return out, np.array(ipiv)
    return _finalize_lapack_order(stored, perm, w), ipiv
