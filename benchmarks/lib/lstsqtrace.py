"""The traced solve of a least-squares deployment (kinds/lstsq.py):
what the per-layer metrics `lstsq.*` read from the xplane.
hostspans.py's tables are fixed and name no span of the `gels` path
(`host_events` filters by them), so this module reads the same planes
with the path's own names, as lib/gridtrace.py does for the grid's.

The slice is one whole solve: it opens with the first `matrix::h2d`
(the constructors hand A over before `gels` opens, and the device
waits for that transfer) and closes with the `gels` driver span; the
device's work may run on past it, to the `block_until_ready` that
ends the wall. Idle time is the lead before the first operation and
the gaps between operations, on the host's clock
(`hostspans.clock_offset_ns`).

Everything returns None where the run has no device trace (a
rehearsal on the CPU) or the program published no such span (a commit
before PR 31): the metric is then left out of the line.
"""

import os

from . import hostspans, reduce_trace
from .tracer import Tracer

#: driver spans open for a whole call: they bound the slice, and cover
#: no idle time
ROOTS = ("gels", "geqrf", "potrf")
#: every span of the cell's route (tier-1 looks for each in a
#: rehearsal, whose conditioning takes the same route)
SPANS = ROOTS + ("matrix::h2d", "gels::gram", "gels::potrf",
                 "gels::select", "gels::geqrf", "gels::unmqr",
                 "gels::trsm")
#: spans of the route the cell's conditioning does not take (CholQR
#: kept): read where they occur, required nowhere
OTHER = ("gels::apply", "gels::refine")
#: the first route's stages: abandoned work when `gels.refactors` says
#: the Gram factor was dropped
FIRST_ROUTE = ("gels::gram", "gels::potrf")


def host_events(pd):
    """The `gels` path's spans in a loaded profile's host planes."""
    return hostspans.host_events(pd, SPANS + OTHER)


class LstsqSlice(hostspans.Slice):
    """hostspans.Slice with the `gels` path's roots and the upload in
    front of them."""

    def __init__(self, planes, spans, offset_ns=0.0):
        self.offset_ns = offset_ns
        self.spans = {}
        for ev in spans:
            self.spans.setdefault(ev[2], []).append((ev[0], ev[1]))
        roots = self.spans.get("gels", [])
        starts = roots + self.spans.get("matrix::h2d", [])
        self.idle = []
        for evs in planes:
            merged = reduce_trace.union_ns(
                [(s - offset_ns, e - offset_ns) for s, e in evs])[1]
            if roots:
                opened = min(s for s, _ in starts)
                closed = max(e for _, e in roots)
                merged = [[opened, opened]] + merged + [[closed, closed]]
            self.idle.append([[e0, s1] for (_, e0), (s1, _)
                              in zip(merged, merged[1:]) if s1 > e0])
        self.idle_ns = sum(e - s for gaps in self.idle for s, e in gaps)


_loaded = {}                        # xplane path -> (mtime, LstsqSlice)


def load(run):
    """The LstsqSlice of the xplane the traced run left under
    `.bench_trace`, once per process; None without a device trace or
    a span of the path in it."""
    if not run.get("trace"):
        return None
    path = Tracer(os.path.join(hostspans.ROOT, ".bench_trace")).xplane()
    if path is None:
        return None
    mtime = os.path.getmtime(path)
    if _loaded.get(path, (None,))[0] != mtime:
        pd = reduce_trace.load(path)
        _loaded[path] = (mtime, LstsqSlice(
            hostspans.device_ops(pd), host_events(pd),
            hostspans.clock_offset_ns(pd)))
    sl = _loaded[path][1]
    return sl if sl.spans and sl.idle_ns else None


def span_sum_s(run, name):
    """Seconds inside spans of `name` in the traced solve."""
    sl = load(run)
    d = sl.durations(name) if sl is not None else []
    return sum(d) / 1e9 if d else None


def idle_select_share(run):
    """Percent of the device's idle time in the traced solve during
    which the host sat in `gels::select` (waiting for the facts of the
    Gram factor, then choosing) or, where the window's counters say a
    first route was abandoned, was dispatching that route's stages."""
    sl = load(run)
    if sl is None:
        return None
    names = ("gels::select",)
    if run["counters"].get("gels.refactors"):
        names += FIRST_ROUTE
    return sl.cover(names)


def solve_roofline(run):
    """The least time the chip could take for one solve (the larger of
    the flops a QR least-squares solve NEEDS over the bf16 peak and
    its bytes over the HBM peak, lib/lstsqcount.py) over the
    device-busy seconds per solve in the traced slice, percent. f32
    at HIGHEST is six bf16 passes, and a route that abandons a Gram
    factor does more than the count: well under 100% by
    construction."""
    from . import lstsqcount, peaks
    t, k = run["trace"], run["records"].get("slice_solves")
    cfg = run["config"]
    count = lstsqcount.COUNTS.get(cfg.get("routine"))
    if not t or not k or count is None or not t["busy_s"]:
        return None
    peak = peaks.peak(run["device_kind"])
    flops, nbytes = count(cfg["m"], cfg["n"], cfg["nrhs"])
    least = max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
    return 100.0 * least / (t["busy_s"] / k)


def refactor_share(run):
    """Percent of the window's `gels` calls that abandoned their first
    route and paid for a second (`gels.refactors` over `gels.solves`,
    counted over the whole window)."""
    solves = run["counters"].get("gels.solves")
    if not solves:
        return None
    return 100.0 * run["counters"].get("gels.refactors", 0) / solves
