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
    driver  driver-entry spans (the `driver` hook below; `note` adds
            the route a driver resolved to)
    step    what the host dispatches inside a driver (getrf::panel,
            getrf::pivots, ...)
    staging host<->device copies and the waits for them (ooc::*,
            matrix::h2d)
    batch   a flusher's turn on one group (batch::flush and children)
    serve   served requests (serve::submit; reqtrace's commits)
    jit     compile-side records (tracing spans, recompile instants,
            backend-compile durations from jax.monitoring)
    tune    autotuner decision marks
    comms   scheduled-collective accounting (dist/ tree schedules)
    metric  counter samples

Everything is gated on ONE module flag read without a lock: disabled,
every hook is a single boolean check (the zero-cost contract drivers
rely on — instrumentation stays wired in production code paths).
Enabled, `span` and the `driver` hook also hold a
`jax.profiler.TraceAnnotation` open for the span's life: under a
profiler session the same spans land in the xplane's host plane, on
the device trace's clock (benchmarks/lib/hostspans.py reads them
there). Records published after the fact (`publish(..., t0, t1)`) are
not bridged.
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
#: jax.profiler.TraceAnnotation, bound by enable() (this module
#: imports no jax before that)
_annotation = None
#: per thread, the driver spans open on it (innermost last): `note`
#: adds the route a driver resolved to the span that is already open
_open = threading.local()
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
    global _enabled, _annotation
    from jax.profiler import TraceAnnotation
    _annotation = TraceAnnotation
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


class _NoSpan:
    """What `span()` hands back while the bus is off: one shared
    object whose enter and exit do nothing."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NO_SPAN = _NoSpan()


class _Span:
    """An open span: published to the bus on exit, and held open as a
    `jax.profiler.TraceAnnotation` meanwhile, so that a profiler
    session records it in the xplane's host plane on the device
    trace's clock. The annotation is the bridge to the profiler, not a
    second store; with no session running it costs half a
    microsecond. Enter and exit on the thread that does the work."""
    __slots__ = ("name", "cat", "args", "t0", "t1", "_ann")

    def __init__(self, name: str, cat: str, args: Dict[str, Any]):
        self.name, self.cat, self.args = name, cat, args

    def __enter__(self):
        self._ann = _annotation(self.name, **self.args)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        publish(self.name, PH_SPAN, self.t0, self.t1, cat=self.cat,
                args=self.args or None)
        return False

    def note(self, **args) -> None:
        """Arguments learnt after the span was opened."""
        self.args.update(args)
        self._ann.set_metadata(**args)


def span(name: str, cat: str = "", **args):
    """RAII span (the trace::Block shape, but into the shared bus and,
    under a profiler session, the profiler's trace). Disabled, the
    shared no-op: no object, no timestamp."""
    if not _enabled:
        return _NO_SPAN
    return _Span(name, cat, args)


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
    stack = getattr(_open, "spans", None)
    if stack is None:
        stack = _open.spans = []
    sp = _Span(op, "jit" if tracing else "driver", a)
    stack.append(sp)
    try:
        with sp:
            yield
    finally:
        stack.pop()
        metrics.observe("%s.%s_seconds" % (op, "trace" if tracing
                                           else "wall"), sp.t1 - sp.t0)


def note(**args) -> None:
    """Add arguments to the innermost driver span open on this thread,
    in its bus record and in its annotation: how a driver says which
    route it resolved to, after its span was opened. No-op with the
    bus off or no driver span open."""
    if not _enabled:
        return
    stack = getattr(_open, "spans", None)
    if stack:
        stack[-1].note(**args)


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


def drain() -> List[Event]:
    """Atomically snapshot and clear (the exporter uses this so
    concurrent publishers cannot land between read and clear)."""
    global _dropped
    with _lock:
        evs = list(_events)
        _events.clear()
        _dropped = 0
    return evs
