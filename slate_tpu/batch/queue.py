"""Request-coalescing micro-batch queue (ISSUE 5 tentpole, part c).

Every dispatch pays a fixed floor (not measured on the current
machine before chip_smoke.py's reading) and XLA's small-problem rates
sit far below MXU peak. For a serving workload — many independent
small/medium problems — the floor can dominate per-request execution.
This queue amortizes it: requests
accumulate per (op, bucket shape, nrhs, dtype) and flush as ONE
batched dispatch when the bucket reaches ``max_batch`` OR has waited
``max_wait_us`` (the BLASX runtime-coalescing trade: a bounded latency
tax buys an O(occupancy) dispatch reduction). Both knobs ride the
tune/ subsystem (frozen defaults in tune/cache.FROZEN: batch/max_batch
= 64, batch/max_wait_us = 2000).

Degradation is graceful by construction: a bucket with one occupant
flushes as a batch of 1 through the SAME vmapped program (bit-identical
results, drivers.py determinism contract), so a sparse stream costs
exactly per-request dispatch, never more.

The RAGGED strategy (ISSUE 15, ``strategy="ragged"`` or an earned
``batch/strategy`` tune entry) drops the bucket dimension from the
coalescing key for the square factorizations/solves: previously-
separate pow2 buckets merge into ONE dispatch stacked at the flush's
max live size (lane-aligned, no pow2 rounding) with a per-element
sizes vector, executed by the masked ragged Pallas kernels
(ops/pallas_kernels.ragged_*) — fewer dispatches AND block-granular
instead of pow2 padding. The FROZEN strategy is "bucket": a cold tune
cache coalesces bit-identically to PR 5.

The padded stacks are built host-side per flush and donated to XLA
where the backend implements donation (drivers._donate_ok) — they are
throwaway copies, so the device may factor in place.

Observability: every flush publishes batch occupancy, padding waste
(element + flop fractions), and dispatches-saved to the obs metrics
registry (batch.* counters/histograms, visible in ``obs.snapshot()``)
and mirrors them in local ``stats()`` for obs-disabled callers.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import bucket as _bucket
from . import drivers as _drivers
from ..obs import events as _ev
from ..obs import ledger as _ledger
from ..obs import metrics as _om
from ..resil import faults as _faults
from ..resil import guard as _guard


class Ticket:
    """One submitted request's handle. ``result()`` blocks until the
    request's bucket has been flushed (forcing the flush itself if the
    queue has no background flusher or the deadline has not fired),
    then returns the CROPPED per-request result."""

    def __init__(self, queue: "CoalescingQueue", key) -> None:
        self._queue = queue
        self._key = key
        self._done = threading.Event()
        self._value: Any = None
        self._error: Optional[BaseException] = None
        #: set at flush time: wall seconds from submit to result
        self.latency_s: Optional[float] = None
        self._t_submit = time.perf_counter()
        #: request-scoped trace context (obs/reqtrace.py Span) handed
        #: in through submit(trace=); None — the default — keeps the
        #: cold route allocation-free. _dispatch stamps the flush
        #: timestamps + flush id onto TRACED tickets only.
        self.trace = None
        self.t_flush: Optional[float] = None
        self.t_dispatch: Optional[float] = None
        self.flush_id: Optional[int] = None

    def _resolve(self, value=None, error=None) -> None:
        self._value = value
        self._error = error
        self.latency_s = time.perf_counter() - self._t_submit
        if self.trace is not None:
            # span closure rides the resolving thread, BEFORE the
            # event fires (a waiter returning from result() must find
            # its span committed); it must never fail a resolution
            try:
                self.trace.on_resolved(self)
            except Exception:
                pass
        self._done.set()

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None):
        """Block (at most `timeout` seconds, None = forever) for this
        request's flushed result. A `timeout` turns the lost-flush
        forever-hang into a clean :class:`TimeoutError` naming the
        bucket (resil/, ISSUE 9); a dead background flusher resolves
        its pending tickets with the death error instead of leaving
        them to hang (see CoalescingQueue._flush_loop). A ticket the
        dying flusher had already POPPED from the bucket (died between
        flush() and _dispatch resolution) is in neither `_pending` nor
        resolved — surface the recorded death error immediately
        (ISSUE 16 satellite) instead of waiting out the full timeout.
        The check runs AFTER the forced flush, so the documented
        degraded-synchronous mode (new submits after a death still
        resolve through result()'s own flush) is untouched."""
        if not self._done.is_set():
            # synchronous fallback: drain my bucket now instead of
            # waiting out the coalescing window
            self._queue.flush(self._key)
        dead = self._queue._flusher_error
        if dead is not None and not self._done.is_set():
            err = RuntimeError(
                "batch background flusher died: %r" % (dead,))
            err.__cause__ = dead
            raise err
        if not self._done.wait(timeout):
            dead = self._queue._flusher_error
            if dead is not None:
                err = RuntimeError(
                    "batch background flusher died: %r" % (dead,))
                err.__cause__ = dead
                raise err
            raise TimeoutError(
                "batched %r request (bucket %r) still pending after "
                "%.4gs — flush lost or dispatch wedged"
                % (self._key[0], self._key[1:], timeout))
        if self._error is not None:
            raise self._error
        return self._value


#: sentinel occupying the (bm, bn) key slots of a ragged bucket — the
#: coalescing key DROPS the shape dimension under the ragged strategy
#: (ISSUE 15), so requests that previously split across pow2 buckets
#: merge into one dispatch; the stacking ceiling is chosen per flush
RAGGED = "ragged"


class CoalescingQueue:
    """The micro-batch dispatcher. Thread-safe; optionally runs a
    daemon flusher thread that enforces the max-wait deadline for
    streams that never call ``result()`` promptly (``background=
    True``). Use as a context manager or call ``close()``.

    ``strategy`` picks the stacking strategy (ISSUE 15): explicit
    ("bucket"/"ragged" or a core/methods.MethodBatchStrategy member)
    wins, else the tuned/frozen ``batch/strategy`` row — FROZEN
    "bucket", so a cold cache coalesces exactly as PR 5 did. Under
    "ragged", the square factorizations/solves (drivers.RAGGED_OPS)
    with a kernel-runnable dtype coalesce per (op, nrhs, dtype) —
    no bucket dimension — and flush as ONE sizes-carrying dispatch
    through the masked ragged Pallas kernels; everything else keeps
    the bucket path."""

    def __init__(self, max_batch: Optional[int] = None,
                 max_wait_us: Optional[int] = None,
                 opts=None, background: bool = False,
                 donate: bool = True, pad_batch: bool = True,
                 strategy=None) -> None:
        from ..core.methods import MethodBatchStrategy, str2method
        from ..tune.select import tuned_int
        self.max_batch = int(max_batch) if max_batch else tuned_int(
            "batch", "max_batch", 64, opts=opts)
        self.max_wait_us = int(max_wait_us) if max_wait_us is not None \
            else tuned_int("batch", "max_wait_us", 2000, opts=opts)
        if strategy is None:
            self._strategy = MethodBatchStrategy.resolve()
        else:
            self._strategy = str2method("batch", strategy) \
                if isinstance(strategy, str) else strategy
            if self._strategy is MethodBatchStrategy.Auto:
                self._strategy = MethodBatchStrategy.resolve()
        #: lane alignment resolved ONCE per queue (like max_batch /
        #: max_wait_us): submit is the serving hot path — a per-call
        #: tune-cache read would put a lock + stats write per request
        self._align = _bucket.batch_align(opts=opts)
        #: kept for the per-flush ragged block-width resolution, so
        #: Option.Tune=False etc. govern that read like every other
        self._opts = opts
        self._donate = donate
        #: round the BATCH dimension up to a power of two with
        #: replicated dummy entries (discarded at crop): without it
        #: every distinct flush occupancy k is a fresh compile, and
        #: the jit cache grows with traffic patterns instead of
        #: staying O(#buckets * log(max_batch))
        self._pad_batch = pad_batch
        self._lock = threading.Lock()
        #: key -> list of pending (ticket, padded_a, padded_b, (m, n))
        self._pending: Dict[tuple, List[tuple]] = {}
        #: key -> perf_counter of the bucket's OLDEST pending request
        self._oldest: Dict[tuple, float] = {}
        self._stats = {"requests": 0, "dispatches": 0,
                       "dispatches_saved": 0, "occupancy_sum": 0,
                       "max_occupancy": 0, "waste_sum": 0.0,
                       "waste_flops_sum": 0.0,
                       "flops_sum": 0.0, "occ_flops_sum": 0.0,
                       "ragged_dispatches": 0,
                       "ragged_flops_saved": 0.0}
        #: ledger step ids for dispatch records: read-and-increment
        #: under _lock (the stats dispatch count increments in a
        #: LATER lock acquisition, so two concurrent flushes reading
        #: it would share a step id)
        self._led_seq = 0
        self._closed = False
        #: set when the background flusher thread died (resil/)
        self._flusher_error: Optional[BaseException] = None
        self._flusher: Optional[threading.Thread] = None
        self._wake = threading.Event()
        if background:
            self._flusher = threading.Thread(
                target=self._flush_loop, name="batch-flusher",
                daemon=True)
            self._flusher.start()

    def _ragged_route(self, op: str, dtype, nrhs: int) -> bool:
        """True when this request coalesces under the ragged strategy:
        the queue resolved Ragged, the op has a ragged kernel route,
        the dtype can execute (hardware or interpreter), and any rhs
        has at least one column (ragged_trsm_eligible's floor — a
        zero-column solve is legal on the bucket path). Anything else
        keeps the bucket path — graceful per-request degradation,
        same as an occupancy-1 bucket."""
        from ..core.methods import MethodBatchStrategy
        if self._strategy is not MethodBatchStrategy.Ragged \
                or op not in _drivers.RAGGED_OPS:
            return False
        if _drivers.OPS[op].has_rhs and nrhs < 1:
            return False
        from ..ops import pallas_kernels as _pk
        return _pk.ragged_supported(dtype)

    # -- submission -------------------------------------------------------

    def submit(self, op: str, a, b=None, trace=None) -> Ticket:
        """Enqueue one problem. `a` is a single (n, n) (or (m, n) for
        geqrf/gels) matrix, `b` an optional (n,) / (n, k) right-hand
        side. Padding to the shape bucket happens here (host-side), so
        flush is a stack + one dispatch.

        `trace` (obs/reqtrace.py Span, serve tier only) rides the
        ticket because submit may flush INLINE (max_batch reached) —
        a context installed after submit returns would miss its own
        dispatch. None (the default) adds nothing to the cold route."""
        if self._closed:
            raise RuntimeError("queue is closed")
        _faults.check("batch_submit", op=op)
        spec = _drivers.OPS.get(op)
        if spec is None:
            raise ValueError(f"unknown batched op {op!r}; have "
                             f"{sorted(_drivers.OPS)}")
        a = np.asarray(a)
        if a.ndim != 2:
            raise ValueError(f"{op} request must be a 2-D matrix, got "
                             f"shape {a.shape}")
        m, n = a.shape
        if op == "gels":
            if m < n:
                raise ValueError("gels is overdetermined-only (m >= n) "
                                 "in the batch layer")
        elif op != "geqrf" and m != n:
            raise ValueError(f"{op} request must be square, got "
                             f"({m}, {n})")
        b2 = None
        nrhs = 0
        if spec.has_rhs:
            if b is None:
                raise ValueError(f"{op} needs a right-hand side")
            b = np.asarray(b)
            b2 = b[:, None] if b.ndim == 1 else b
            if b2.shape[0] != m:
                raise ValueError(f"rhs rows {b2.shape[0]} != matrix "
                                 f"rows {m}")
            if b2.dtype != a.dtype:
                # fail-fast: a mismatched rhs stacked with well-formed
                # ones would np.result_type-promote the whole stack
                # and fail EVERY co-batched ticket at dispatch time —
                # one malformed request must not poison its bucket
                raise ValueError(
                    f"{op} rhs dtype {b2.dtype} != matrix dtype "
                    f"{a.dtype}; cast explicitly before submit")
            nrhs = b2.shape[1]
        elif b is not None:
            raise ValueError(f"{op} takes no right-hand side")
        if self._ragged_route(op, a.dtype, nrhs):
            # ragged strategy (ISSUE 15): NO per-request padding here
            # — the stacking ceiling is a property of the flush (the
            # max live size, bucket.ragged_ceiling), so _dispatch_
            # ragged pads once at flush. SNAPSHOT the operands: the
            # bucket path copies at submit (pad_square), and a caller
            # mutating its array between submit and flush must see
            # the same submitted-value semantics here
            key = (op, RAGGED, RAGGED, nrhs, a.dtype.str)
            pa = np.array(a, copy=True)
            pb = None if b2 is None else np.array(b2, copy=True)
        else:
            if op in ("geqrf", "gels") and m != n:
                bm, bn = _bucket.rect_buckets(m, n,
                                              align=self._align)
                pa = _bucket.pad_rect(a, bm, bn, spec.pad_mode)
            else:
                bm = bn = _bucket.bucket_for(m, align=self._align)
                pa = _bucket.pad_square(a, bm, spec.pad_mode)
            pb = None if b2 is None \
                else _bucket.pad_rhs(b2, bm, nrhs)
            key = (op, bm, bn, nrhs, pa.dtype.str)
        ticket = Ticket(self, key)
        if trace is not None:
            ticket.trace = trace
        flush_now = False
        with self._lock:
            pend = self._pending.setdefault(key, [])
            pend.append((ticket, pa, pb, (m, n)))
            self._oldest.setdefault(key, time.perf_counter())
            if len(pend) >= self.max_batch:
                flush_now = True
        if flush_now:
            # a full bucket is dispatched in the CALLER'S thread
            with _ev.span("batch::inline_flush", cat="batch", op=op):
                self.flush(key)
        elif self._flusher is not None:
            self._wake.set()
        return ticket

    # -- flushing ---------------------------------------------------------

    def flush(self, key=None) -> int:
        """Dispatch one bucket (or every bucket with key=None).
        Returns the number of dispatches issued."""
        with self._lock:
            keys = [key] if key is not None else list(self._pending)
            taken = []
            for k in keys:
                entries = self._pending.pop(k, None)
                self._oldest.pop(k, None)
                if entries:
                    taken.append((k, entries))
        for k, entries in taken:
            self._dispatch(k, entries)
        return len(taken)

    def _flush_loop(self) -> None:
        try:
            while not self._closed:
                self._wake.wait(
                    timeout=self.max_wait_us / 2e6 or 0.001)
                self._wake.clear()
                if self._closed:
                    return
                # `busy` lets a plan target the tick that actually
                # holds pending work (an idle loop spins every
                # max_wait_us/2, so unscoped occurrence counts are
                # timing-dependent)
                _faults.check("flusher", busy=bool(self._oldest))
                now = time.perf_counter()
                due = [k for k, t0 in list(self._oldest.items())
                       if now - t0 >= self.max_wait_us / 1e6]
                for k in due:
                    self.flush(k)
        except BaseException as e:
            self._on_flusher_death(e)

    def _on_flusher_death(self, e: BaseException) -> None:
        """The background flusher died: fail every pending ticket with
        the death error instead of leaving their waiters to hang
        (resil/, ISSUE 9 satellite). The queue stays usable in
        degraded synchronous mode — result() always forces its own
        bucket's flush — and the death is published + counted."""
        self._flusher_error = e
        with self._lock:
            taken = list(self._pending.items())
            self._pending.clear()
            self._oldest.clear()
        err = RuntimeError(
            "batch background flusher died: %r" % (e,))
        err.__cause__ = e
        for _k, entries in taken:
            for t, *_rest in entries:
                t._resolve(error=err)
        _guard._count("resil.flusher_deaths")
        from ..obs import events as obs_events
        if obs_events.enabled():
            from ..obs import metrics as om
            om.inc("resil.flusher_deaths")
            obs_events.instant("resil::flusher_death", cat="resil",
                               error=str(e)[:120],
                               failed=sum(len(v) for _, v in taken))

    def _pad_batch_pow2(self, stack, rhs):
        """Round the BATCH dimension up to a power of two with
        replicated dummy entries (discarded at crop; __init__ doc:
        occupancy variations reuse compiled programs). Returns
        (stack, rhs, pad_count)."""
        if not self._pad_batch:
            return stack, rhs, 0
        from ..core.tiles import next_pow2
        k = stack.shape[0]
        kp = next_pow2(k)
        if kp > k:
            stack = np.concatenate(
                [stack, np.repeat(stack[-1:], kp - k, 0)])
            if rhs is not None:
                rhs = np.concatenate(
                    [rhs, np.repeat(rhs[-1:], kp - k, 0)])
        return stack, rhs, kp - k

    def _dispatch_guarded(self, op: str, fn):
        """The dispatch retry ladder BOTH strategies share (resil/,
        ISSUE 9): under an active fault plan every attempt passes the
        "batch" injection site; without one the first attempt runs
        bare (steady state stays check-free) and only a transient —
        injected OR real — failure enters the bounded retry.
        Exhaustion (or a non-transient error) propagates to the
        caller, which resolves every co-batched ticket with it."""
        def _once():
            _faults.check("batch", op=op)
            return fn()

        if _faults.active() is not None:
            return _guard.retry(_once, "batch", op=op)
        try:
            return fn()
        except Exception as e:
            if not _guard.is_transient(e):
                raise
            return _guard.retry_after_failure(_once, "batch", e,
                                              op=op)

    def _dispatch(self, key, entries) -> None:
        """One group's turn on the thread that flushes it. With the
        obs bus on the turn is a `batch::flush` span whose children
        (`batch::stack`, `batch::dispatch`, `batch::fetch`,
        `batch::resolve`) split it at the ledger record's timestamps,
        and every ticket's wait from submit to here is observed."""
        run = self._dispatch_ragged if key[1] == RAGGED \
            else self._dispatch_bucket
        if not _ev.enabled():
            return run(key, entries)
        now = time.perf_counter()
        for e in entries:
            _om.observe("batch.queue_wait_seconds",
                        now - e[0]._t_submit)
        with _ev.span("batch::flush", cat="batch", op=key[0],
                      bucket=key[1], occupancy=len(entries)):
            return run(key, entries)

    def _dispatch_bucket(self, key, entries) -> None:
        op, bm, bn, nrhs, _dt = key
        spec = _drivers.OPS[op]
        tickets = [e[0] for e in entries]
        batch_pad = 0
        # flight-recorder record per dispatch (obs/ledger.py; one
        # boolean when the FROZEN obs/ledger row keeps it off): the
        # host-side stack/pad build is `stage`, the batched dispatch
        # + result fetch is `factor`. A traced flush (any serve
        # ticket carrying a reqtrace span) shares the same two
        # timestamps and additionally gets a flush id + linkage
        # record — reqtrace off means `traced` is False for free.
        led_on = _ledger.enabled()
        traced = any(t.trace is not None for t in tickets)
        fid = None
        if traced:
            from ..obs import reqtrace as _rt
            fid = _rt.next_flush_id()
        t_led = time.perf_counter() if (led_on or traced) else 0.0
        span = _ev.span
        try:
            with span("batch::stack", cat="batch"):
                stack = np.stack([e[1] for e in entries])
                rhs = np.stack([e[2] for e in entries]) \
                    if spec.has_rhs else None
                stack, rhs, batch_pad = self._pad_batch_pow2(stack,
                                                             rhs)
            t_stage = time.perf_counter() if (led_on or traced) \
                else 0.0
            with span("batch::dispatch", cat="batch"):
                out = self._dispatch_guarded(
                    op, lambda: _drivers._dispatch(
                        op, stack, rhs, donate=self._donate))
            parts = out if isinstance(out, tuple) else (out,)
            with span("batch::fetch", cat="batch"):
                hosts = [np.asarray(o) for o in parts]
            if led_on:
                t_done = time.perf_counter()
                with self._lock:
                    seq = self._led_seq
                    self._led_seq += 1
                rep = _bucket.stack_report([e[3] for e in entries],
                                           bm, bn)
                meta = {"op": op, "occupancy": len(entries),
                        "strategy": "bucket",
                        "ceiling": bm,
                        "waste_flops": round(
                            rep["padding_waste_flops"], 4)}
                if traced:
                    meta["traces"] = [t.trace.trace_id
                                      for t in tickets
                                      if t.trace is not None][:16]
                _ledger.append(
                    "batch.dispatch", step=seq,
                    phases={"stage": t_stage - t_led,
                            "factor": t_done - t_stage},
                    meta=meta)
            with span("batch::resolve", cat="batch"):
                for i, (t, _pa, _pb, (m, n)) in enumerate(entries):
                    if t.trace is not None:
                        t.t_flush = t_led
                        t.t_dispatch = t_stage
                        t.flush_id = fid
                    t._resolve(value=_crop(op, [h[i] for h in hosts],
                                           m, n, nrhs))
            if traced:
                _rt.record_flush(
                    op, t_led, time.perf_counter(), fid,
                    [t.trace.trace_id for t in tickets
                     if t.trace is not None],
                    occupancy=len(entries), strategy="bucket")
        except BaseException as e:      # resolve-or-hang: every ticket
            for t in tickets:           # must learn its fate
                t._resolve(error=e)
            self._record(key, entries, batch_pad)
            return
        self._record(key, entries, batch_pad)

    def _dispatch_ragged(self, key, entries) -> None:
        """One RAGGED flush (ISSUE 15): pick the ceiling from THIS
        flush's live sizes (max, rounded to lcm(align, blk) — the
        only jit-cache key), zero-pad each operand to it (the kernels
        rebuild validity-masked padding in-kernel, so pad content is
        irrelevant), stack, and dispatch once with the sizes vector.
        Retry/ledger/crop wiring mirrors the bucket path."""
        op, _bm, _bn, nrhs, _dt = key
        spec = _drivers.OPS[op]
        tickets = [e[0] for e in entries]
        batch_pad = 0
        from ..ops import pallas_kernels as _pk
        blk = _pk.ragged_blk(opts=self._opts)
        led_on = _ledger.enabled()
        traced = any(t.trace is not None for t in tickets)
        fid = None
        if traced:
            from ..obs import reqtrace as _rt
            fid = _rt.next_flush_id()
        t_led = time.perf_counter() if (led_on or traced) else 0.0
        span = _ev.span
        try:
            sizes = [e[3][1] for e in entries]
            with span("batch::stack", cat="batch"):
                ceil = _bucket.ragged_ceiling(sizes, blk=blk,
                                              align=self._align)
                stack = np.stack(
                    [_bucket.pad_square(e[1], ceil, "zero")
                     for e in entries])
                rhs = np.stack([_bucket.pad_rhs(e[2], ceil, nrhs)
                                for e in entries]) \
                    if spec.has_rhs else None
                stack, rhs, batch_pad = self._pad_batch_pow2(stack,
                                                             rhs)
                szarr = np.asarray(
                    sizes + [sizes[-1]] * batch_pad, np.int32)
            t_stage = time.perf_counter() if (led_on or traced) \
                else 0.0
            with span("batch::dispatch", cat="batch"):
                out = self._dispatch_guarded(
                    op, lambda: _drivers.ragged_dispatch(
                        op, stack, szarr, rhs, blk=blk,
                        donate=self._donate))
            parts = out if isinstance(out, tuple) else (out,)
            with span("batch::fetch", cat="batch"):
                hosts = [np.asarray(o) for o in parts]
            if led_on:
                t_done = time.perf_counter()
                with self._lock:
                    seq = self._led_seq
                    self._led_seq += 1
                rep = _bucket.ragged_report(sizes, blk,
                                            align=self._align)
                meta = {"op": op, "occupancy": len(entries),
                        "strategy": "ragged", "ceiling": ceil,
                        "waste_flops": round(
                            rep["padding_waste_flops"], 4)}
                if traced:
                    meta["traces"] = [t.trace.trace_id
                                      for t in tickets
                                      if t.trace is not None][:16]
                _ledger.append(
                    "batch.dispatch", step=seq,
                    phases={"stage": t_stage - t_led,
                            "factor": t_done - t_stage},
                    meta=meta)
            with span("batch::resolve", cat="batch"):
                for i, (t, _pa, _pb, (m, n)) in enumerate(entries):
                    if t.trace is not None:
                        t.t_flush = t_led
                        t.t_dispatch = t_stage
                        t.flush_id = fid
                    t._resolve(value=_crop(op, [h[i] for h in hosts],
                                           m, n, nrhs))
            if traced:
                _rt.record_flush(
                    op, t_led, time.perf_counter(), fid,
                    [t.trace.trace_id for t in tickets
                     if t.trace is not None],
                    occupancy=len(entries), strategy="ragged")
        except BaseException as e:      # resolve-or-hang, as above
            for t in tickets:
                t._resolve(error=e)
            self._record(key, entries, batch_pad, ragged_blk=blk)
            return
        self._record(key, entries, batch_pad, ragged_blk=blk)

    def _record(self, key, entries, batch_pad: int = 0,
                ragged_blk: Optional[int] = None) -> None:
        op, bm, bn, nrhs, _dt = key
        ns = [e[3] for e in entries]
        saved = None
        if ragged_blk is not None:
            rep = _bucket.ragged_report([n for (_m, n) in ns],
                                        ragged_blk,
                                        align=self._align)
            sched = rep.pop("scheduled_flops")
            saved = rep.pop("flops_saved")
            label = RAGGED
        else:
            rep = _bucket.stack_report(ns, bm, bn)
            sched = len(ns) * bm * float(bn) ** 2
            label = "%dx%d" % (bm, bn)
        k = rep["occupancy"]
        with self._lock:
            s = self._stats
            s["requests"] += k
            s["dispatches"] += 1
            s["dispatches_saved"] += k - 1
            s["occupancy_sum"] += k
            s["max_occupancy"] = max(s["max_occupancy"], k)
            s["waste_sum"] += rep["padding_waste"]
            s["waste_flops_sum"] += rep["padding_waste_flops"]
            s["flops_sum"] += sched
            s["occ_flops_sum"] += k * sched
            if saved is not None:
                s["ragged_dispatches"] += 1
                s["ragged_flops_saved"] += saved
        from ..obs import events as obs_events
        if obs_events.enabled():
            from ..obs import metrics as om
            om.inc("batch.requests", k)
            om.inc("batch.dispatches")
            om.inc("batch.dispatches_saved", k - 1)
            if batch_pad:
                om.inc("batch.pad_entries", batch_pad)
            if saved is not None:
                om.inc("batch.ragged_dispatches")
                om.inc("batch.ragged_flops_saved", int(saved))
            om.observe("batch.occupancy", k)
            om.observe("batch.padding_waste", rep["padding_waste"])
            om.observe("batch.padding_waste_flops",
                       rep["padding_waste_flops"])
            obs_events.instant("batch:%s" % op, cat="driver",
                               occupancy=k, bucket=label,
                               padding_waste=round(
                                   rep["padding_waste"], 4))

    # -- bookkeeping ------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        """Local mirror of the obs batch.* metrics (works with the
        bus disabled): requests, dispatches, dispatches_saved, mean/max
        occupancy, mean padding-waste fractions, the FLOPS-WEIGHTED
        mean occupancy (each dispatch weighted by its scheduled cubic
        extent — the occupancy the MXU actually sees, ISSUE 15
        satellite), and the ragged dispatch/flops-saved mirrors.

        ``pending_by_key`` (ISSUE 16 satellite) breaks the NOT-yet-
        flushed work down per coalescing key — count, queued flops
        (sum of true-extent m*n^2 cubic work, the useful-work measure
        admission control weighs, not the padded schedule), and the
        age of the key's oldest request — so the serve/ admission
        layer sees queue COMPOSITION, not just totals."""
        # ONE clock read per snapshot (ISSUE 18 satellite): every
        # age_s below derives from this single `now`, so the ages
        # within one stats() snapshot are mutually consistent — the
        # difference between two keys' ages equals the difference
        # between their oldest-submit times exactly (pinned by
        # tests); a per-key clock read inside the lock would skew
        # them by the iteration time
        now = time.perf_counter()
        with self._lock:
            s = dict(self._stats)
            s["pending_by_key"] = {
                k: {"count": len(v),
                    "queued_flops": float(sum(
                        m * float(n) ** 2 for _t, _a, _b, (m, n) in v)),
                    "age_s": now - self._oldest.get(k, now)}
                for k, v in self._pending.items() if v}
        d = max(s["dispatches"], 1)
        s["mean_occupancy"] = s.pop("occupancy_sum") / d
        s["mean_padding_waste"] = s.pop("waste_sum") / d
        s["mean_padding_waste_flops"] = s.pop("waste_flops_sum") / d
        flops = s.pop("flops_sum")
        occf = s.pop("occ_flops_sum")
        s["mean_occupancy_weighted"] = occf / flops if flops > 0 \
            else 0.0
        return s

    def pending(self) -> int:
        with self._lock:
            return sum(len(v) for v in self._pending.values())

    def close(self) -> None:
        """Flush everything and stop the background flusher."""
        self._closed = True
        self._wake.set()
        self.flush()
        if self._flusher is not None:
            self._flusher.join(timeout=1.0)

    def __enter__(self) -> "CoalescingQueue":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _crop(op: str, outs, m: int, n: int, nrhs: int):
    """Cut one request's logical result out of the padded batched
    output (the bucket padding contract makes the crop exact)."""
    if op == "potrf":
        return outs[0][:n, :n]
    if op in ("getrf", "geqrf"):
        return outs[0][:m, :n], outs[1][: min(m, n)]
    if op in ("posv", "gesv", "potrs", "getrs"):
        return outs[0][:n, :nrhs]
    if op == "gels":
        return outs[0][:n, :nrhs]
    if op == "heev":
        return outs[0][:n], outs[1][:n, :n]
    raise ValueError(f"unknown op {op!r}")


def run(op: str, mats, rhs=None, max_batch: Optional[int] = None,
        opts=None, strategy=None) -> list:
    """One-shot convenience: coalesce a list of heterogeneous
    problems through a fresh queue and return their results in
    submission order. This is the route api/lapack_compat.py takes
    for ndim>2 inputs. ``strategy`` threads through to the queue
    (None = the tuned/frozen ``batch/strategy`` route)."""
    q = CoalescingQueue(max_batch=max_batch, opts=opts,
                        background=False, strategy=strategy)
    with q:
        if rhs is None:
            tickets = [q.submit(op, a) for a in mats]
        else:
            tickets = [q.submit(op, a, b) for a, b in zip(mats, rhs)]
        q.flush()
        return [t.result() for t in tickets]
