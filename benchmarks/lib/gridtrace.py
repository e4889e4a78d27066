"""The traced solve of a grid deployment (kinds/grid.py): what the
per-layer metrics `grid.*` read from the xplane of a run on several
chips. reduce_trace.py averages busy time over the device planes and
sums launches over them, and hostspans.py's tables name no span of the
grid path; this module reads the same planes with the grid path's own
names, chip by chip.

The slice is one whole solve: it opens with the first `grid::place`
(the upload of A, before any driver span) and closes with the last root
driver span. A chip's idle time in it is the lead before its first
operation, the gaps between its operations and the tail after the last,
on the host's clock (`hostspans.clock_offset_ns`).

Everything returns None where the run has no device trace (a rehearsal
on the CPU) or the program published no such span (a commit before
PR 27): the metric is then left out of the line.
"""

import os

from . import hostspans, reduce_trace
from .tracer import Tracer

#: driver spans open for a whole call: they bound the slice, and cover
#: no idle time
ROOTS = ("posv", "potrf", "potrs")
#: every span of the grid path (tier-1 looks for each in a rehearsal)
SPANS = ROOTS + ("grid::place", "matrix::h2d", "posv::prep",
                 "posv::factor", "posv::solve")
#: HLO opcodes that move data between chips; the asynchronous ones are
#: a `-start` and a `-done` operation on the device's line
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")


def opcode(name):
    """`%all-gather-start.3 = (f32[..]) all-gather-start(...)` ->
    `all-gather-start`."""
    if " = " not in name:
        return name.split(".")[0].lstrip("%")
    return reduce_trace.short_name(name).split(" ")[1]


def is_collective(op):
    for half in ("-start", "-done"):
        if op.endswith(half):
            op = op[:-len(half)]
    return op in COLLECTIVES


def host_events(pd):
    """The grid path's spans in a loaded profile's host planes."""
    return hostspans.host_events(pd, SPANS)


class GridSlice(hostspans.Slice):
    """hostspans.Slice with the grid path's roots: per chip the idle
    pieces of the solve, on the host's clock, and the host spans by
    name."""

    def __init__(self, planes, spans, offset_ns=0.0):
        self.offset_ns = offset_ns
        self.spans = {}
        for ev in spans:
            self.spans.setdefault(ev[2], []).append((ev[0], ev[1]))
        roots = [iv for n in ROOTS for iv in self.spans.get(n, ())]
        starts = roots + self.spans.get("grid::place", [])
        self.busy_ns, self.idle = [], []
        for evs in planes:
            total, merged = reduce_trace.union_ns(
                [(s - offset_ns, e - offset_ns) for s, e in evs])
            self.busy_ns.append(total)
            if roots:
                opened = min(s for s, _ in starts)
                closed = max(e for _, e in roots)
                merged = [[opened, opened]] + merged + [[closed, closed]]
            self.idle.append([[e0, s1] for (_, e0), (s1, _)
                              in zip(merged, merged[1:]) if s1 > e0])
        self.idle_ns = sum(e - s for gaps in self.idle for s, e in gaps)


def read(pd):
    """{"slice": GridSlice, "busy_s": [per chip], "collective_s": [per
    chip], "launches": [per chip]} of a loaded profile; None without a
    device plane that ran anything."""
    busy, coll, launches = [], [], []
    for p in pd.planes:
        if not p.name.startswith(reduce_trace.DEVICE_PREFIX):
            continue
        lines = {ln.name: ln for ln in p.lines
                 if ln.name in (reduce_trace.MODULES, reduce_trace.OPS)}
        if reduce_trace.OPS not in lines:
            continue
        evs = reduce_trace._events(lines[reduce_trace.OPS], opcode)
        if not evs:
            continue
        busy.append(reduce_trace.union_ns([(s, e) for s, e, _ in evs])[0]
                    / 1e9)
        coll.append(sum(sec for op, sec
                        in reduce_trace.self_times(evs).items()
                        if is_collective(op)))
        mods = lines.get(reduce_trace.MODULES)
        launches.append(len(list(mods.events)) if mods is not None else 0)
    if not busy:
        return None
    sl = GridSlice(hostspans.device_ops(pd), host_events(pd),
                   hostspans.clock_offset_ns(pd))
    return {"slice": sl, "busy_s": busy, "collective_s": coll,
            "launches": launches}


_loaded = {}                        # xplane path -> (mtime, read(pd))


def load(run):
    """`read` of the xplane the traced run left under `.bench_trace`,
    once per process; None without a device trace."""
    if not run.get("trace"):
        return None
    path = Tracer(os.path.join(hostspans.ROOT, ".bench_trace")).xplane()
    if path is None:
        return None
    mtime = os.path.getmtime(path)
    if _loaded.get(path, (None,))[0] != mtime:
        _loaded[path] = (mtime, read(reduce_trace.load(path)))
    return _loaded[path][1]


def _solves(run):
    return run["records"].get("slice_solves")


def collective_share(run):
    """Self time of the collective operations over device-busy time,
    percent, mean of the chips."""
    t = load(run)
    if t is None:
        return None
    shares = [100.0 * c / b for c, b in zip(t["collective_s"],
                                            t["busy_s"]) if b]
    return sum(shares) / len(shares) if shares else None


def busy_imbalance(run):
    """(largest - smallest device-busy seconds of the chips) over their
    mean, percent."""
    t = load(run)
    if t is None or len(t["busy_s"]) < 2:
        return None
    b = t["busy_s"]
    return 100.0 * (max(b) - min(b)) / (sum(b) / len(b))


def launches_per_solve(run):
    """`XLA Modules` events of the traced solve on ONE chip's plane
    (every chip runs each SPMD program once; the largest count is
    taken)."""
    t, n = load(run), _solves(run)
    if t is None or not n:
        return None
    return max(t["launches"]) / n


def solve_roofline(run):
    """The least time the grid's chips could take for one solve (the
    larger of the flops the solve NEEDS over the chips' summed bf16
    peak and its bytes over their summed HBM peak, lib/opcount.py)
    over the device-busy seconds per solve, mean of the chips,
    percent."""
    from . import opcount, peaks
    t, n = load(run), _solves(run)
    cfg = run["config"]
    count = opcount.COUNTS.get(cfg.get("routine"))
    if t is None or not n or count is None:
        return None
    chips = len(t["busy_s"])
    peak = peaks.peak(run["device_kind"])
    flops, nbytes = count(cfg["n"], cfg["nrhs"])
    least, _bound = opcount.roofline_seconds(
        flops, nbytes, {"flops_per_s": chips * peak["flops_per_s"],
                        "bytes_per_s": chips * peak["bytes_per_s"]})
    busy = sum(t["busy_s"]) / chips / n
    return 100.0 * least / busy if busy else None


def idle_cover(run, names):
    """Percent of the chips' idle nanoseconds in the solve during which
    a span of `names` was open on some thread."""
    t = load(run)
    if t is None or not t["slice"].spans:
        return None
    return t["slice"].cover(names)
