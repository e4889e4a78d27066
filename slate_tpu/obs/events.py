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
            matrix::h2d), and the `*_ready` spans that stay open
            until an upload is on the device (`watch_ready`)
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

Two mechanisms serve the staging layer (PR 36). `watch_ready` hands a
device array that was just handed to the runtime to ONE daemon thread,
`obs-ready`, which holds a span open until the array is ready: the
transfer's own seconds, which no thread of the program waits for.
`clock_beacon`, called just BEFORE an upload of `BEACON_MIN_BYTES` or
more is handed over, launches the clock beacon `obs::clock_sync` on
the caller's own thread, and only while a profiler session is
running: a one-element program launched and waited for inside a span,
which brackets the skew between the profiler's host and device planes
from both sides (benchmarks/lib/clock2.py). And a span opened with
`resident=<counter>` adds to that counter the bytes the PROCESS's
resident set grew by meanwhile (`/proc/self/statm`; Linux only): what
a host-side copy into or out of never-touched pages faults in.
The store is a bounded ring (EVENT_CAP) so an always-on bus cannot
grow without bound; drops are counted, never silent.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import os
import queue
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

#: an upload of fewer bytes launches no clock beacon: the beacon costs
#: the constructing thread 0.8 ms, half of what the v5e's link takes
#: for 16 MiB, and the served tier's 160 uploads a second must not
#: each launch a program
BEACON_MIN_BYTES = 16 << 20

#: where a process's resident pages are read (`_Span(resident=)`)
_STATM = "/proc/self/statm"
_PAGE_BYTES = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 0

_enabled = False
#: jax.profiler.TraceAnnotation, bound by enable() (this module
#: imports no jax before that)
_annotation = None
#: (compiled one-element program, its argument on jax.devices()[0]),
#: made by enable(): the clock beacon must never compile at first use.
#: None: no beacon is ever launched
_beacon = None
#: the `obs-ready` thread's queue while there is such a thread (made
#: by the first `watch_ready` with the bus on, ended by `disable`)
_ready_q: "Optional[queue.SimpleQueue]" = None
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


def enable(beacon: bool = True) -> None:
    """Turn the bus on (also installs the jax.monitoring compile-time
    listener once — obs/metrics.py). Unless `beacon` is false it also
    compiles the clock beacon, once a process, so that no beacon
    compiles where it is launched: that initialises the backend and
    runs a one-element program on `jax.devices()[0]`. It has to be
    here while benchmarks/run.py calls nothing else before it counts
    the window's compiles and traces the window's first solve. A
    caller that keeps the bus on and never profiles, or must not touch
    the backend yet, passes `beacon=False`: it then launches none."""
    global _enabled, _annotation, _beacon
    from jax.profiler import TraceAnnotation
    _annotation = TraceAnnotation
    if beacon and _beacon is None:
        _beacon = _compile_beacon()
    _enabled = True
    from . import metrics
    metrics.install_jax_monitoring()


def disable() -> None:
    """Turn the bus off. The `obs-ready` thread, if there is one,
    finishes what it was handed and ends."""
    global _enabled, _ready_q
    _enabled = False
    with _lock:
        q, _ready_q = _ready_q, None
    if q is not None:
        q.put(None)


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
    microsecond. Enter and exit on the thread that does the work.

    `resident` names a counter: the bytes the process's resident set
    grew by while the span was open (pages of a mapping touched for
    the first time: a fresh buffer written, an `np.zeros` array read)
    are noted on the span as `touched_bytes` and added to that
    counter; a set that shrank counts nothing. The reading is the
    WHOLE process's (`_resident_bytes`): it is this span's own only
    where nothing else maps or frees much meanwhile, so it belongs on
    a span that is open one at a time, around the threads that copy,
    and not on each of them. Where the platform has no
    `/proc/self/statm` the argument does nothing."""
    __slots__ = ("name", "cat", "args", "t0", "t1", "_ann", "resident",
                 "_res0")

    def __init__(self, name: str, cat: str, args: Dict[str, Any], *,
                 resident: Optional[str] = None):
        self.name, self.cat, self.args = name, cat, args
        self.resident = resident

    def __enter__(self):
        self._ann = _annotation(self.name, **self.args)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        if self.resident:
            self._res0 = _resident_bytes()
        return self

    def __exit__(self, *exc):
        if self.resident and self._res0 is not None:
            from . import metrics
            n = max(_resident_bytes() - self._res0, 0)
            self.note(touched_bytes=n)
            metrics.inc(self.resident, n)
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        publish(self.name, PH_SPAN, self.t0, self.t1, cat=self.cat,
                args=self.args or None)
        return False

    def note(self, **args) -> None:
        """Arguments learnt after the span was opened."""
        self.args.update(args)
        self._ann.set_metadata(**args)


def span(name: str, cat: str = "", *, resident: Optional[str] = None,
         **args):
    """RAII span (the trace::Block shape, but into the shared bus and,
    under a profiler session, the profiler's trace). Disabled, the
    shared no-op: no object, no timestamp, nothing read. `resident`:
    the counter that the resident set's growth under the span adds to
    (`_Span`)."""
    if not _enabled:
        return _NO_SPAN
    return _Span(name, cat, args, resident=resident)


def _resident_bytes() -> Optional[int]:
    """Bytes of this process resident in memory, from the second
    field of `/proc/self/statm`; None where there is no such file.
    Why not the thread's own minor faults (`getrusage(RUSAGE_THREAD)`,
    as PR 36 first had it): the sealed host every chip number comes
    from (gVisor) reports 0 of them whatever is touched, and this is
    what it does report (PERF.md, PR 36). It is sized in bytes, so a
    transparent huge page counts for what it maps."""
    try:
        # unbuffered: three system calls where `open()` makes seven,
        # and one costs 30 us on that host
        fd = os.open(_STATM, os.O_RDONLY)
        try:
            return int(os.read(fd, 128).split()[1]) * _PAGE_BYTES
        finally:
            os.close(fd)
    except (OSError, IndexError, ValueError):
        return None


def _compile_beacon():
    """The clock beacon: a one-element program on `jax.devices()[0]`,
    where the in-core constructors put their arrays, compiled ahead of
    time under the name `obs_clock_sync` (`jit_obs_clock_sync` in a
    device trace, so that a launch count can leave it out) and run
    once here, so that the first beacon pays no first-call set-up.
    After that nothing but `_clock_sync` runs it."""
    import jax
    import numpy as np

    def obs_clock_sync(x):
        return x + 1

    x = jax.device_put(np.zeros((1,), np.float32), jax.devices()[0])
    run = jax.jit(obs_clock_sync).lower(x).compile()
    run(x).block_until_ready()
    return run, x


def _clock_sync() -> None:
    """Launch the beacon and wait for it inside `obs::clock_sync`:
    on a device with nothing else to run the execution lies inside
    the span to within the launch and wake-up latencies, so the span
    brackets (device clock - host clock) from both sides."""
    run, x = _beacon
    with span("obs::clock_sync", cat="trace"):
        run(x).block_until_ready()


def _wait_ready(name: str, arr, args: Dict[str, Any],
                handed: float) -> None:
    """One watched upload, on the `obs-ready` thread: the span that is
    open until `arr` is ready."""
    import jax
    args["queued_us"] = (time.perf_counter() - handed) * 1e6
    with _Span(name, "staging", args) as sp:
        try:
            jax.block_until_ready(arr)
        except RuntimeError:        # deleted or donated meanwhile
            sp.note(gone=1)


def _ready_loop(q: "queue.SimpleQueue") -> None:
    """The `obs-ready` thread: what it was handed (callables), in
    order, until `None`. Spans of one name never overlap, so their sum
    is the seconds during which at least one such upload was in
    flight; a finished item's reference to its array goes with it."""
    while True:
        item = q.get()
        if item is None:
            return
        item()
        del item


def _ready_queue() -> "queue.SimpleQueue":
    """The `obs-ready` thread's queue, the thread made at first use."""
    global _ready_q
    q = _ready_q
    if q is None:
        with _lock:
            q = _ready_q
            if q is None:
                q = _ready_q = queue.SimpleQueue()
                threading.Thread(target=_ready_loop, args=(q,),
                                 name="obs-ready", daemon=True).start()
    return q


def watch_ready(name: str, arr, **args) -> None:
    """A span `name` (category `staging`) that stays open until the
    device array `arr`, just handed to the runtime, is ready: opened,
    waited in and closed by the `obs-ready` thread, never by the
    caller, which goes on at once (observing must not change the
    program). The span notes `queued_us`, what the thread took the
    array after the hand-over by, and `gone=1` where the array was
    deleted or donated before it was ready; the thread then drops its
    reference. No-op with the bus off: `arr` is not touched and no
    thread is made."""
    if not _enabled:
        return
    _ready_queue().put(functools.partial(
        _wait_ready, name, arr, args, time.perf_counter()))


def _profiling() -> bool:
    """Whether a `jax.profiler` session is running in this process
    (`start_trace` until `stop_trace`): only then is there an xplane
    for a beacon to be read in."""
    try:
        from jax._src.profiler import _profile_state
    except ImportError:             # moved: then no beacon, no bracket
        return False
    return _profile_state.profile_session is not None


def clock_beacon(nbytes: int) -> None:
    """One clock beacon, launched and waited for on the CALLER's
    thread just before it hands an upload of `nbytes` to the runtime;
    no-op with the bus off, for fewer than `BEACON_MIN_BYTES`, where
    `enable(beacon=False)` compiled none, and while no profiler
    session is running: a bus that is merely on launches nothing and
    waits for nothing. Under a profiler this is a launch and a wait
    the program does not otherwise make (0.74-0.86 ms on an idle v5e:
    the runtime takes 0.45 ms to acknowledge any program), and since
    the device runs programs in launch order the wait also drains
    whatever was queued on device 0: THE TRACED SOLVE IS SYNCHRONISED
    AT EACH LARGE UPLOAD, which an untraced one is not. It is the one
    place where observing adds to what the observed thread does. It was
    tried twice on the `obs-ready` thread (PERF.md, PR 36): launched
    after the hand-over it queued 23-84 ms behind the programs that
    waited for the upload, because the device runs programs in launch
    order; asked for before the hand-over, the thread still launched
    it 4-27 ms late, waiting out the interpreter's switch interval
    while the caller dispatched. A beacon is only a bracket when the
    thread that launches it is the thread that would otherwise have
    launched what comes next."""
    if not _enabled or nbytes < BEACON_MIN_BYTES or _beacon is None \
            or not _profiling():
        return
    _clock_sync()


def flush_ready(timeout: Optional[float] = None) -> bool:
    """Wait until the `obs-ready` thread has closed the span of every
    upload handed to it so far (a reader of the bus calls this before
    it sums them). True when it has, or there is no such thread."""
    q = _ready_q
    if q is None:
        return True
    done = threading.Event()
    q.put(done.set)
    return done.wait(timeout)


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
