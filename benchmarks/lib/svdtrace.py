"""The traced solve of a singular value decomposition deployment
(kinds/svd.py): what the per-layer metrics `svd.*` read from the
xplane and from the window's counters. hostspans.py's tables are fixed
and name no span of the `svd` path, so this module reads the same
planes with the path's own names, as lib/heevtrace.py does for `heev`
(whose counter reader it reuses: the eigensolver runs inside
`svd::eig` with its own spans and counters).

The slice is one whole solve: it opens with the first `matrix::h2d`
(the constructor hands A over before `svd` opens) and closes with the
`svd` driver span; the device's work may run on past it, to the
`block_until_ready` that ends the wall.

Everything returns None where the run has no device trace (a rehearsal
on the CPU) or the program published no such span or counter (a commit
before PR 39): the metric is then left out of the line.
"""

import os

from . import heevtrace, hostspans, reduce_trace, uploadtrace
from .tracer import Tracer

#: driver spans open for a whole call: they bound the slice, and cover
#: no idle time
ROOTS = ("svd",)
#: every span of the cell's route (tier-1 looks for each in a
#: rehearsal, which takes the same route)
SPANS = ROOTS + ("matrix::h2d", "svd::prep", "svd::polar", "svd::form",
                 "svd::eig", "svd::compose", "svd::agenda") \
    + tuple(s for s in heevtrace.SPANS if s.startswith("heev::")
            and s != "heev::prep")
#: the host's reads: the eigensolver's of a split's sizes, and the
#: driver's of the polar's flags
AGENDA = ("heev::agenda", "svd::agenda")
#: the program that forms H = sym(U_p^T A): the launch before it is
#: the one `svd::polar` dispatched
FORM = "jit__svd_form"
EIG = "jit_dc_"


def host_events(pd):
    """The `svd` path's spans in a loaded profile's host planes."""
    return hostspans.host_events(pd, SPANS)


def slice_of(pd):
    """The slice of a loaded profile: uploadtrace.UploadSlice with the
    `svd` root, on hostspans' clock."""
    return uploadtrace.UploadSlice(hostspans.device_ops(pd),
                                   host_events(pd),
                                   hostspans.clock_offset_ns(pd), "svd")


def launches(pd):
    """[(start ns, program name, device seconds)] of the `XLA Modules`
    events of the device planes, in the order they ran."""
    out = []
    for p in pd.planes:
        if not p.name.startswith(reduce_trace.DEVICE_PREFIX):
            continue
        for ln in p.lines:
            if ln.name == reduce_trace.MODULES:
                out += [(float(e.start_ns), e.name.split("(")[0],
                         float(e.duration_ns) / 1e9) for e in ln.events]
    return sorted(out)


def busy_by_step(ordered):
    """{"polar", "eig", "all"}: device seconds of the launches that
    `svd::polar` dispatched (the program that ran last before each
    `jit__svd_form`: the eigensolver's own `jit_dc_sign_<n>`), of the
    eigensolver's programs after them (every other `jit_dc_*`), and of
    all programs. None where no `jit__svd_form` ran."""
    polar = [i - 1 for i, (_, name, _) in enumerate(ordered)
             if name == FORM and i]
    if not polar:
        return None
    return {"polar": sum(ordered[i][2] for i in polar),
            "eig": sum(sec for i, (_, name, sec) in enumerate(ordered)
                       if name.startswith(EIG) and i not in polar),
            "all": sum(sec for _, _, sec in ordered)}


_loaded = {}                        # xplane path -> (mtime, slice, steps)


def load(run):
    """(slice, `busy_by_step`) of the xplane the traced run left under
    `.bench_trace`, once per process; None without a device trace or a
    span of the path in it."""
    if not run.get("trace"):
        return None
    path = Tracer(os.path.join(hostspans.ROOT, ".bench_trace")).xplane()
    if path is None:
        return None
    mtime = os.path.getmtime(path)
    if _loaded.get(path, (None,))[0] != mtime:
        pd = reduce_trace.load(path)
        _loaded[path] = (mtime, slice_of(pd), busy_by_step(launches(pd)))
    _, sl, steps = _loaded[path]
    return (sl, steps) if sl.spans.get("svd") and sl.idle_ns else None


def idle_agenda_share(run):
    """Percent of the device's idle time in the traced solve during
    which the host sat in a read (`heev::agenda`, `svd::agenda`)."""
    got = load(run)
    return None if got is None else got[0].cover(AGENDA)


def _busy_share(run, step):
    got = load(run)
    if got is None or got[1] is None or not got[1]["all"]:
        return None
    return 100.0 * got[1][step] / got[1]["all"]


def polar_busy_share(run):
    """Device seconds of the launch `svd::polar` dispatched over the
    seconds of all programs in the traced solve, percent: the share of
    the step this route adds in front of the eigensolver."""
    return _busy_share(run, "polar")


def eig_busy_share(run):
    """Device seconds of the eigensolver's programs (`jit_dc_*`, the
    polar's launch apart) over those of all programs, percent."""
    return _busy_share(run, "eig")


def solve_roofline(run):
    """The least time the chip could take for one solve (the flops a
    decomposition with both sets of vectors NEEDS over the bf16 peak,
    or its bytes over the HBM peak, lib/svdcount.py) over the
    device-busy seconds per solve in the traced slice, percent. The
    count is Golub-Reinsch's 21 n^3 whatever implements the solve; a
    QDWH-SVD does several times that, in f32 at HIGHEST (six bf16
    passes), so this is a percent or two at most."""
    from . import peaks, svdcount
    t, k = run["trace"], run["records"].get("slice_solves")
    cfg = run["config"]
    count = svdcount.COUNTS.get(cfg.get("routine"))
    if not t or not k or count is None or not t["busy_s"]:
        return None
    peak = peaks.peak(run["device_kind"])
    flops, nbytes = count(cfg["n"])
    least = max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
    return 100.0 * least / (t["busy_s"] / k)


def polar_iters_per_solve(run):
    """`svd.polar_iters` over `svd.solves`, counted over the whole
    window: the steps the polar iteration of A takes."""
    solves = run["counters"].get("svd.solves")
    if not solves or "svd.polar_iters" not in run["counters"]:
        return None
    return run["counters"]["svd.polar_iters"] / solves


#: `heev.polar_iters` over `heev.splits` inside this cell
eig_polar_iters_per_split = heevtrace.polar_iters_per_split
