"""What the staging layer's own spans and counters say (PR 36): the
seconds an upload was in flight, the share of the device's idle time
under it on the two-sided clock, and the bytes the host's copies
touched for the first time. The per-layer metrics `*.upload_ready_s`,
`*.idle_upload_share`, `*.clock_bracket_us`, `stream.h2d_ready_s` and
`*_fault_gb` read through here.

hostspans.py's tables are fixed and name none of these spans, so this
module reads the same planes with its own names
(`hostspans.host_events(pd, names)`), as lib/lstsqtrace.py and
lib/heevtrace.py do, and moves the device's intervals by
`clock2.offset_ns` where those use `hostspans.clock_offset_ns`.

The in-core slice is one whole solve: it opens with the first
`matrix::h2d` (the constructors hand A over before the driver's span
opens, and the device waits for that transfer) and closes with the
cell's root driver span. `matrix::h2d_ready` is open on the
`obs-ready` thread from just after the hand-over until the array is on
the chip, so the idle time under it is the transfer's, whatever else
the host was doing meanwhile: `lstsq.idle_select_share` covers the
same milliseconds of `tall-gels` from the main thread's side.

Everything returns None where the run has no device trace (a
rehearsal on the CPU), no beacon in it, or the program published no
such span or counter (a commit before PR 36): the metric is then left
out of the line.
"""

import os

from . import clock2, hostspans, reduce_trace
from .tracer import Tracer

READY = "matrix::h2d_ready"

#: cell -> (root driver span of its in-core slice, the spans of this
#: PR that a traced run of the cell publishes); tier-1 looks for each
#: in a rehearsal. The streamed and grid cells have no in-core slice
ROOTS = {"incore-gesv": "gesv", "tall-gels": "gels", "incore-heev": "heev"}
SPANS = {
    **{cell: ("matrix::h2d", READY, clock2.BEACON_SPAN) for cell in ROOTS},
    "stream-posv": ("ooc::h2d_ready", "ooc::d2h"),
    "grid-posv": ("matrix::h2d",),
}
#: of those, the spans that note `touched_bytes` (`span(resident=)`)
TOUCHED = {"stream-posv": "ooc::d2h", "grid-posv": "matrix::h2d"}


class UploadSlice(hostspans.Slice):
    """hostspans.Slice between the first `matrix::h2d` and the end of
    the `root` driver span."""

    def __init__(self, planes, spans, offset_ns, root):
        self.offset_ns = offset_ns
        self.spans = {}
        for ev in spans:
            self.spans.setdefault(ev[2], []).append((ev[0], ev[1]))
        roots = self.spans.get(root, [])
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


def read(pd, root):
    """(slice, (below, above)) of a loaded profile, or None where it
    holds no beacon, no `root` span or no `matrix::h2d_ready`."""
    bracket = clock2.bracket_ns(pd)
    if bracket is None:
        return None
    sl = UploadSlice(hostspans.device_ops(pd),
                     hostspans.host_events(pd, {root, "matrix::h2d", READY}),
                     clock2.offset_ns(bracket), root)
    if not (sl.spans.get(root) and sl.spans.get(READY) and sl.idle_ns):
        return None
    return sl, bracket


_loaded = {}                        # (xplane path, root) -> (mtime, read)


def load(run, root):
    """`read` of the xplane that the traced run left under
    `.bench_trace`, once per process; None without a device trace."""
    if not run.get("trace"):
        return None
    path = Tracer(os.path.join(hostspans.ROOT, ".bench_trace")).xplane()
    if path is None:
        return None
    mtime, key = os.path.getmtime(path), (path, root)
    if _loaded.get(key, (None,))[0] != mtime:
        _loaded[key] = (mtime, read(reduce_trace.load(path), root))
    return _loaded[key][1]


def idle_upload_share(run, root):
    """Percent of the device's idle time in the traced solve during
    which an upload through the matrix constructors was in flight
    (`matrix::h2d_ready` open), on `clock2`'s offset."""
    got = load(run, root)
    return None if got is None else got[0].cover((READY,))


def clock_bracket_us(run, root):
    """Width of the bracket the traced slice's beacons put on (device
    clock - host clock), microseconds: what the shares above can be
    off by. Hundreds mean a beacon waited behind other work."""
    got = load(run, root)
    return None if got is None else (got[1][1] - got[1][0]) / 1e3


def span_s_per_solve(run, name):
    """Bus seconds inside `name` spans over the window, per solve."""
    s, n = run["spans"].get(name), run["records"].get("solves")
    if not s or not n:
        return None
    return s / n


def touched_gb_per_solve(run, counter):
    """Bytes under `counter` over the window, per solve, in GB: what
    the process's resident set grew by while the counter's span was
    open (`obs/events.py _Span(resident=)`, from `/proc/self/statm`),
    which is the pages faulted in meanwhile, by any thread. The
    program first counted its threads' minor faults (`getrusage`);
    the chip's sealed host (gVisor) reports none of those, and a
    metric that read them printed 0.0 there whatever was touched
    (PERF.md, PR 36). None where the program has no such counter: a
    commit before PR 36, or a host without `/proc/self/statm`."""
    n = run["records"].get("solves")
    if counter not in run["counters"] or not n:
        return None
    return run["counters"][counter] / n / 1e9
