"""The program's spans beside the device's operations, on one clock.

With the obs bus on, every `obs.events.span` and driver hook of the
program is also a `jax.profiler.TraceAnnotation`, so the traced run's
xplane holds them in its `/host:*` planes, one line per thread, on the
timeline of the `/device:TPU:*` planes. This module reads both and
says what the host was doing while the device sat idle.

The two kinds of plane share an epoch but not quite a clock: on the
v5e the device planes' stamps ran 0.5 to 1.3 ms behind the host
planes', by an amount fixed within a profiler session and different in
the next (PERF.md, PR 25), which is more than most gaps of an in-core
solve last. `clock_offset_ns` measures it in each xplane from the
runtime's own events (a program cannot start on the device before the
host began to enqueue it) and the device's intervals are moved by it
before anything is compared; tools/clock_check.py prints the bracket.

Device idle = the gaps between the merged `XLA Ops` intervals of a
device plane (`reduce_trace.union_ns`: the gaps `idle_gaps` names by
the program that ended them) and, where the slice holds root driver
spans, the lead from the first root span's opening to the first
operation and the tail from the last operation to the last root
span's end: a streamed solve spends 4.8 of its 18.5 s before its first
operation (PERF.md, PR 25), which the gaps alone would hide. A share
is a percent of those idle nanoseconds. Shares of different names may overlap (a
worker uploads while the main thread waits for it) and so may sum past
100; covered plus `idle_uncovered` is 100 only for the whole table.

Everything returns None on a run without a device trace (`--rehearse`
on a CPU, or a program that published no such span): the metric is
then left out of the line.
"""

import os

from . import reduce_trace
from .tracer import Tracer

HOST_PREFIX = "/host:"
ENQUEUE = "DoEnqueueProgram"    # the runtime's host event of one launch
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

INCORE, STREAM, SERVE = "incore-gesv", "stream-posv", "serve-steady"

#: driver spans that stay open for a whole call: they say that a solve
#: was running, not what the host did in it, so they cover no idle time
ROOTS = {
    "gesv": (INCORE,), "getrf": (INCORE,),
    "posv_ooc": (STREAM,), "potrf_ooc": (STREAM,), "potrs_ooc": (STREAM,),
}

#: span name -> the cells whose traced slice holds it (tier-1 checks
#: that a rehearsal of the cell publishes each). A name listed for no
#: cell is read where it occurs and required nowhere.
SPANS = {
    **ROOTS,
    "matrix::h2d": (INCORE, SERVE),
    "getrf::prep": (INCORE,), "getrf::panel": (INCORE,),
    "getrf::pivots": (INCORE,), "getrf::update": (INCORE,),
    "getrf::reorder": (INCORE,), "getrf::info": (INCORE,),
    "getrs": (INCORE,),
    "ooc::alloc": (STREAM,),
    "ooc::h2d": (STREAM,), "ooc::h2d_pack": (STREAM,),
    "ooc::h2d_put": (STREAM,), "ooc::prefetch": (STREAM,),
    "ooc::wait_stage": (STREAM,), "ooc::wait_write": (STREAM,),
    "ooc::d2h": (STREAM,), "ooc::writeback": (STREAM,),
    "serve::submit": (SERVE,), "batch::flush": (SERVE,),
    "batch::stack": (SERVE,), "batch::dispatch": (SERVE,),
    "batch::fetch": (SERVE,), "batch::resolve": (SERVE,),
    "batch::inline_flush": (),      # over capacity only
}


def host_events(pd, names=None):
    """[(start ns, end ns, name, {argument: value})] of every event in
    the `/host:*` planes of a loaded profile whose name is in `names`
    (default: the SPANS table)."""
    names = SPANS if names is None else names
    out = []
    for p in pd.planes:
        if not p.name.startswith(HOST_PREFIX):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name in names:
                    s = float(e.start_ns)
                    out.append((s, s + float(e.duration_ns), e.name,
                                dict(e.stats)))
    return out


def device_ops(pd):
    """[[(start ns, end ns)]]: the `XLA Ops` intervals (the modules
    where a plane has no such line) of each device plane that ran
    anything."""
    out = []
    for p in pd.planes:
        if not p.name.startswith(reduce_trace.DEVICE_PREFIX):
            continue
        lines = {ln.name: ln for ln in p.lines
                 if ln.name in (reduce_trace.MODULES, reduce_trace.OPS)}
        ln = lines.get(reduce_trace.OPS) or lines.get(reduce_trace.MODULES)
        evs = [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
               for e in ln.events] if ln is not None else []
        if evs:
            out.append(evs)
    return out


def clock_offset_ns(pd):
    """Device planes' clock minus host planes' clock in this xplane,
    nanoseconds (negative: the device's stamps are early). The runtime
    leaves a host event `DoEnqueueProgram` per launch and the device an
    `XLA Modules` event per execution, both with the launch's
    `run_id`; no execution starts before its enqueue began, so the
    smallest (execution start - enqueue start) over the pairs is an
    upper bound on the offset, and it is met to within the shortest
    enqueue-to-start latency of the session (tens of microseconds:
    hundreds of pairs lie within 10 us of the smallest). The smallest
    is taken among differences that two more lie within 100 us above
    (`floor_ns`): one streamed solve of seven sessions held a single
    pair 8 ms under all others and under the bound its completion
    events give from below, a mispairing. 0.0 where the xplane holds
    no such pair."""
    enqueued = {}
    for p in pd.planes:
        if p.name.startswith(HOST_PREFIX):
            for ln in p.lines:
                for e in ln.events:
                    if e.name == ENQUEUE:
                        rid = dict(e.stats).get("run_id")
                        if rid is not None:     # its earliest event
                            enqueued[rid] = min(float(e.start_ns),
                                                enqueued.get(rid, 1e30))
    leads = []
    for p in pd.planes:
        if p.name.startswith(reduce_trace.DEVICE_PREFIX) and enqueued:
            for ln in p.lines:
                if ln.name == reduce_trace.MODULES:
                    for e in ln.events:
                        at = enqueued.get(dict(e.stats).get("run_id"))
                        if at is not None:
                            leads.append(float(e.start_ns) - at)
    return floor_ns(leads)


def floor_ns(values, within=100e3, support=2):
    """The smallest of `values` that `support` others lie within
    `within` above; the plain smallest of fewer than that many, 0.0 of
    none."""
    values = sorted(values)
    for i in range(len(values) - support):
        if values[i + support] - values[i] <= within:
            return values[i]
    return values[0] if values else 0.0


def overlap_ns(a, b):
    """Length of the intersection of two lists of disjoint, ascending
    [start, end] pieces."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


class Slice:
    """One traced slice: the idle pieces of each device plane and the
    host spans by name. `planes` is [[(start, end)]] per device plane,
    `spans` is [(start, end, name, ...)], `offset_ns` what the device's
    clock is ahead of the host's by (`clock_offset_ns`)."""

    def __init__(self, planes, spans, offset_ns=0.0):
        self.offset_ns = offset_ns
        self.spans = {}
        for ev in spans:
            self.spans.setdefault(ev[2], []).append((ev[0], ev[1]))
        roots = [iv for n in ROOTS for iv in self.spans.get(n, ())]
        self.idle = []              # per plane: idle pieces, host clock
        for evs in planes:
            merged = reduce_trace.union_ns(
                [(s - offset_ns, e - offset_ns) for s, e in evs])[1]
            if roots:               # the lead and the tail of a solve
                opened = min(s for s, _ in roots)
                closed = max(e for _, e in roots)
                merged = [[opened, opened]] + merged + [[closed, closed]]
            self.idle.append([[e0, s1] for (_, e0), (s1, _)
                              in zip(merged, merged[1:]) if s1 > e0])
        self.idle_ns = sum(e - s for gaps in self.idle for s, e in gaps)

    def covered_ns(self, names):
        """Idle nanoseconds during which a span of `names` was open on
        any thread."""
        merged = reduce_trace.union_ns(
            [iv for n in names for iv in self.spans.get(n, ())])[1]
        return sum(overlap_ns(gaps, merged) for gaps in self.idle)

    def cover(self, names):
        if not self.idle_ns:
            return None
        return 100.0 * self.covered_ns(names) / self.idle_ns

    def uncovered(self):
        """Percent of the idle time with no span of the table open,
        the root driver spans not counted."""
        got = self.cover([n for n in SPANS if n not in ROOTS])
        return None if got is None else 100.0 - got

    def durations(self, name):
        return [e - s for s, e in self.spans.get(name, ())]


_loaded = {}                        # xplane path -> (mtime, Slice)


def load(run):
    """The Slice of the xplane that the traced run left under
    `.bench_trace`, loaded once per process; None without a device
    trace or a span of the table in it."""
    if not run.get("trace"):
        return None
    path = Tracer(os.path.join(ROOT, ".bench_trace")).xplane()
    if path is None:
        return None
    mtime = os.path.getmtime(path)
    if _loaded.get(path, (None,))[0] != mtime:
        pd = reduce_trace.load(path)
        _loaded[path] = (mtime, Slice(device_ops(pd), host_events(pd),
                                      clock_offset_ns(pd)))
    sl = _loaded[path][1]
    return sl if sl.spans and sl.idle_ns else None


def idle_cover(run, names):
    """Percent of the device's idle nanoseconds during which at least
    one span of `names` was open on any thread. Shares of different
    names may overlap and sum past 100."""
    sl = load(run)
    return None if sl is None else sl.cover(names)


def idle_uncovered(run):
    """Percent of the device's idle nanoseconds during which no span
    of the table, other than a root driver span, was open."""
    sl = load(run)
    return None if sl is None else sl.uncovered()


def _durations(run, name):
    sl = load(run)
    return sl.durations(name) if sl is not None else []


def span_sum_s(run, name):
    """Seconds inside spans of `name` in the slice, summed over
    threads; None where the slice holds none."""
    d = _durations(run, name)
    return sum(d) / 1e9 if d else None


def span_mean_ms(run, name):
    d = _durations(run, name)
    return sum(d) / len(d) / 1e6 if d else None


def span_max_ms(run, name):
    d = _durations(run, name)
    return max(d) / 1e6 if d else None
