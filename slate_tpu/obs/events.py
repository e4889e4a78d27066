"""Process-wide structured event bus (ISSUE 3 tentpole part 1).

One store for every observability record in the process: the RAII
trace blocks of utils/trace.py, the autotuner's decision marks
(tune/stats.py), and the driver hooks below all publish here. The
reference keeps three disjoint stores (Trace.cc's per-thread vectors,
the opts timer maps, the tuner counters); merging them is what makes
the Perfetto export (obs/export.py) one coherent timeline and lets
obs/report.py attribute a run without stitching.

Events carry thread identity (OOC host staging records from worker
threads land in the same stream — the reference Trace.cc:359 merges
per-thread vectors the same way at finish) and a category:

    trace   utils/trace.py blocks and marks
    phase   driver phase timers (trace.phases / Timers.phase)
    driver  driver-entry spans (the `driver` hook below)
    jit     compile-side records (tracing spans, recompile instants,
            backend-compile durations from jax.monitoring)
    tune    autotuner decision marks
    comms   scheduled-collective accounting (dist/ tree schedules)
    metric  counter samples

Everything is gated on ONE module flag read without a lock: disabled,
every hook is a single boolean check (the zero-cost contract drivers
rely on — instrumentation stays wired in production code paths).
The store is a bounded ring (EVENT_CAP) so an always-on bus cannot
grow without bound; drops are counted, never silent.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

#: span kinds, Chrome-trace phase letters ("X" complete span,
#: "i" instant, "C" counter sample)
PH_SPAN = "X"
PH_INSTANT = "i"
PH_COUNTER = "C"
#: Perfetto flow-event phase letters (ISSUE 18): export.py emits
#: these to link a traced request's serve::request span to the
#: batch::flush slice it rode — never published onto the bus itself
PH_FLOW_START = "s"
PH_FLOW_END = "f"

#: bounded ring capacity; oldest events drop first (counted).
#: deque(maxlen) keeps publish O(1) — a list trim would memmove the
#: whole ring under the lock on every publish once full
EVENT_CAP = 100_000

_enabled = False
_lock = threading.Lock()
_events: "collections.deque[Event]" = collections.deque(
    maxlen=EVENT_CAP)
_dropped = 0


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    ph: str                    # PH_SPAN / PH_INSTANT / PH_COUNTER
    t0: float                  # perf_counter seconds
    t1: float                  # == t0 for instants/counters
    tid: int
    thread: str
    cat: str = ""
    args: Optional[Dict[str, Any]] = None

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


def enable() -> None:
    """Turn the bus on (also installs the jax.monitoring compile-time
    listener once — obs/metrics.py)."""
    global _enabled
    _enabled = True
    from . import metrics
    metrics.install_jax_monitoring()


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def publish(name: str, ph: str = PH_INSTANT, t0: Optional[float] = None,
            t1: Optional[float] = None, cat: str = "",
            args: Optional[Dict[str, Any]] = None) -> None:
    """Append one event (no-op when disabled). Timestamps default to
    now; spans pass their own (t0, t1)."""
    if not _enabled:
        return
    global _dropped
    t = time.perf_counter() if t0 is None else t0
    th = threading.current_thread()
    ev = Event(name=name, ph=ph, t0=t, t1=(t if t1 is None else t1),
               tid=threading.get_ident(), thread=th.name, cat=cat,
               args=args)
    with _lock:
        if len(_events) == EVENT_CAP:
            _dropped += 1               # deque maxlen evicts oldest
        _events.append(ev)


@contextlib.contextmanager
def span(name: str, cat: str = "", **args):
    """RAII span published on exit (the trace::Block shape, but into
    the shared bus)."""
    if not _enabled:
        yield
        return
    t0 = time.perf_counter()
    try:
        yield
    finally:
        publish(name, PH_SPAN, t0, time.perf_counter(), cat=cat,
                args=args or None)


def instant(name: str, cat: str = "", **args) -> None:
    publish(name, PH_INSTANT, cat=cat, args=args or None)


def counter(name: str, value, cat: str = "metric") -> None:
    """One counter sample (Perfetto renders these as tracks)."""
    publish(name, PH_COUNTER, cat=cat, args={"value": value})


def _tracing() -> bool:
    """True when called under a jax trace (the Python body of a jitted
    driver runs only while (re)compiling — a cache hit never reaches
    it, which is exactly the recompile signal metrics.record_trace
    keys on). No `except`: if jax drops this name the detector must
    fail a test, not read "never tracing"."""
    from jax._src import core
    return not core.trace_state_clean()


@contextlib.contextmanager
def driver(op: str, shape: Optional[Tuple[int, ...]] = None,
           dtype=None, **args):
    """Driver-entry hook: every public linalg/dist driver wraps its
    body in one of these. Publishes a span (cat 'driver' eagerly,
    'jit' while tracing), bumps the per-driver invocation counter, and
    feeds the recompile detector with (op, shape, dtype) — the key a
    jit cache miss is attributed to. One boolean check when disabled."""
    if not _enabled:
        yield
        return
    from . import metrics
    tracing = _tracing()
    sig = (tuple(shape) if shape is not None else None,
           str(dtype) if dtype is not None else None)
    a = dict(args)
    if shape is not None:
        a["shape"] = "x".join(str(s) for s in shape)
    if dtype is not None:
        a["dtype"] = str(dtype)
    if tracing:
        # a trace entry is a compile, not an execution: it feeds the
        # recompile detector and jit.traces, never the calls counter
        # (which must agree with the report's eager `calls` column)
        metrics.record_trace(op, sig)
    else:
        metrics.inc("driver.%s.calls" % op)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        t1 = time.perf_counter()
        cat = "jit" if tracing else "driver"
        publish(op, PH_SPAN, t0, t1, cat=cat, args=a or None)
        metrics.observe("%s.%s_seconds" % (op, "trace" if tracing
                                           else "wall"), t1 - t0)


def instrument_driver(op: str):
    """Decorator form of `driver` for public driver entry points:
    pulls (shape, dtype) for the recompile key from the first
    TiledMatrix-like or array argument. Disabled cost: one boolean
    check, then a plain call."""
    import functools

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not _enabled:
                return fn(*args, **kwargs)
            shape = dtype = None
            for a in args:
                if hasattr(a, "mtype") and hasattr(a, "data"):
                    shape = tuple(a.data.shape)
                    dtype = getattr(a.data, "dtype", None)
                    break
                if hasattr(a, "shape") and hasattr(a, "dtype"):
                    shape, dtype = tuple(a.shape), a.dtype
                    break
            with driver(op, shape=shape, dtype=dtype):
                return fn(*args, **kwargs)
        return wrapper
    return deco


def events(cat: Optional[str] = None) -> List[Event]:
    """Snapshot (copy) of the ring, optionally filtered by category."""
    with _lock:
        evs = list(_events)
    if cat is not None:
        evs = [e for e in evs if e.cat == cat]
    return evs


def count() -> int:
    """Ring occupancy without copying it."""
    with _lock:
        return len(_events)


def dropped() -> int:
    """Lifetime ring evictions. Reads under `_lock` like count()/
    events() — `_dropped` is written under the lock at publish time,
    and a torn read here would let report() print a drop count that
    disagrees with the ring snapshot taken one line earlier (ISSUE 14
    satellite: the accessors are consistent, drops are never
    under-reported to the attribution warning)."""
    with _lock:
        return _dropped


def clear() -> None:
    global _dropped
    with _lock:
        _events.clear()
        _dropped = 0


def drain(cats: Optional[Tuple[str, ...]] = None) -> List[Event]:
    """Atomically snapshot and clear (trace.finish / export use this
    so concurrent publishers cannot land between read and clear).
    With `cats`, only events in those categories are removed and
    returned — trace.finish() drains just the legacy trace store's
    categories so it cannot destroy a concurrent obs session's
    driver/compile records. The drop counter tracks lifetime ring
    evictions and resets only on a FULL drain/clear; a partial drain
    deliberately leaves it (the evictions still happened)."""
    global _dropped
    with _lock:
        if cats is None:
            evs = list(_events)
            _events.clear()
            _dropped = 0
            return evs
        evs = [e for e in _events if e.cat in cats]
        kept = [e for e in _events if e.cat not in cats]
        _events.clear()
        _events.extend(kept)
    return evs
