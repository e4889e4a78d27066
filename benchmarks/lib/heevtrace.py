"""The traced solve of a symmetric eigenproblem deployment
(kinds/heev.py): what the per-layer metrics `heev.*` read from the
xplane and from the window's counters. hostspans.py's tables are fixed
and name no span of the `heev` path (`host_events` filters by them), so
this module reads the same planes with the path's own names, as
lib/lstsqtrace.py does for `gels`.

The slice is one whole solve: it opens with the first `matrix::h2d`
(the constructor hands A over before `heev` opens) and closes with the
`heev` driver span; the device's work may run on past it, to the
`block_until_ready` that ends the wall. Idle time is the lead before
the first operation and the gaps between operations, on the host's
clock (`hostspans.clock_offset_ns`).

Everything returns None where the run has no device trace (a rehearsal
on the CPU) or the program published no such span or counter (a commit
before PR 33): the metric is then left out of the line.
"""

import os

from . import hostspans, reduce_trace
from .tracer import Tracer

#: driver spans open for a whole call: they bound the slice, and cover
#: no idle time
ROOTS = ("heev",)
#: every span of the cell's route (tier-1 looks for each in a
#: rehearsal, which takes the same route)
SPANS = ROOTS + ("matrix::h2d", "heev::prep", "heev::split",
                 "heev::agenda", "heev::leaf", "heev::vectors")


def host_events(pd):
    """The `heev` path's spans in a loaded profile's host planes."""
    return hostspans.host_events(pd, SPANS)


class HeevSlice(hostspans.Slice):
    """hostspans.Slice with the `heev` path's root and the upload in
    front of it."""

    def __init__(self, planes, spans, offset_ns=0.0):
        self.offset_ns = offset_ns
        self.spans = {}
        for ev in spans:
            self.spans.setdefault(ev[2], []).append((ev[0], ev[1]))
        roots = self.spans.get("heev", [])
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


def modules(pd):
    """{program name: [launches, device seconds]} over the `XLA
    Modules` events of the device planes (`jit_dc_sign_8192(123)` ->
    `jit_dc_sign_8192`)."""
    out = {}
    for p in pd.planes:
        if not p.name.startswith(reduce_trace.DEVICE_PREFIX):
            continue
        for ln in p.lines:
            if ln.name == reduce_trace.MODULES:
                for e in ln.events:
                    got = out.setdefault(e.name.split("(")[0], [0, 0.0])
                    got[0] += 1
                    got[1] += float(e.duration_ns) / 1e9
    return out


_loaded = {}                        # xplane path -> (mtime, slice, modules)


def load(run):
    """(slice, `modules`) of the xplane the traced run left
    under `.bench_trace`, once per process; None without a device
    trace or a span of the path in it."""
    if not run.get("trace"):
        return None
    path = Tracer(os.path.join(hostspans.ROOT, ".bench_trace")).xplane()
    if path is None:
        return None
    mtime = os.path.getmtime(path)
    if _loaded.get(path, (None,))[0] != mtime:
        pd = reduce_trace.load(path)
        _loaded[path] = (mtime, HeevSlice(
            hostspans.device_ops(pd), host_events(pd),
            hostspans.clock_offset_ns(pd)), modules(pd))
    _, sl, mods = _loaded[path]
    return (sl, mods) if sl.spans.get("heev") and sl.idle_ns else None


def idle_agenda_share(run):
    """Percent of the device's idle time in the traced solve during
    which the host sat in `heev::agenda`: waiting for a split's sizes
    with nothing else dispatched behind it."""
    got = load(run)
    return None if got is None else got[0].cover(("heev::agenda",))


def root_busy_share(run):
    """Device seconds of the programs at the full size n (the root
    split's, and a lopsided split's fallback: `jit_dc_*_<n>`) over the
    seconds of all programs in the traced solve, percent."""
    got = load(run)
    if got is None:
        return None
    mods = got[1]
    total = sum(sec for _, sec in mods.values())
    tail = "_%d" % run["config"]["n"]
    full = sum(sec for name, (_, sec) in mods.items()
               if name.startswith("jit_dc_") and name.endswith(tail))
    return 100.0 * full / total if total and full else None


def solve_roofline(run):
    """The least time the chip could take for one solve (the flops an
    eigendecomposition with all vectors NEEDS over the bf16 peak, or
    its bytes over the HBM peak, lib/heevcount.py) over the
    device-busy seconds per solve in the traced slice, percent. The
    count is the tridiagonalisation route's 9 n^3, whatever
    implements the solve: spectral divide and conquer does several
    times that, in f32 at HIGHEST (six bf16 passes), so this is a few
    percent at most."""
    from . import heevcount, peaks
    t, k = run["trace"], run["records"].get("slice_solves")
    cfg = run["config"]
    count = heevcount.COUNTS.get(cfg.get("routine"))
    if not t or not k or count is None or not t["busy_s"]:
        return None
    peak = peaks.peak(run["device_kind"])
    flops, nbytes = count(cfg["n"])
    least = max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
    return 100.0 * least / (t["busy_s"] / k)


def _counter(run, name):
    return run["counters"].get(name)


def polar_iters_per_split(run):
    """`heev.polar_iters` over `heev.splits`, counted over the whole
    window: the polar iterations a split's sign function takes."""
    splits = _counter(run, "heev.splits")
    if not splits:
        return None
    return _counter(run, "heev.polar_iters") / splits


def pad_rows_share(run):
    """Percent of the rows the window's splits worked on that were
    padding: (padded - true) over padded, of `heev.split_rows_padded`
    (each split's bucket) and `heev.split_rows_true` (its size)."""
    padded = _counter(run, "heev.split_rows_padded")
    if not padded:
        return None
    return 100.0 * (padded - _counter(run, "heev.split_rows_true")) / padded
