"""The traced solve of a mixed-precision deployment (kinds/mixed.py):
what the per-layer metrics `mixed.*` read from the xplane and from the
window's counters. hostspans.py's tables are fixed and name no span of
the `gesv_mixed` path, so this module reads the same planes with the
path's own names, as lib/svdtrace.py does for `svd`.

The slice is one whole solve: the operands are on the device before
it, so it opens and closes with the `gesv_mixed` driver span (the host
waits inside it, at `gesv_mixed::verdict`, for everything it
dispatched).

Everything returns None where the run has no device trace (a rehearsal
on the CPU) or the program published no such span, program or counter
(a commit before PR 42): the metric is then left out of the line.
"""

import os

from . import hostspans, reduce_trace, svdtrace, uploadtrace
from .tracer import Tracer

ROOT = "gesv_mixed"
#: the one place the host waits: the read of the flag and the count
VERDICT = "gesv_mixed::verdict"
#: opened only when the host decided for the f32 solve
FALLBACK = "gesv_mixed::fallback"
#: every span of the cell's route (tier-1 looks for each in a
#: rehearsal, and for FALLBACK on a system that does not converge)
SPANS = (ROOT, "gesv_mixed::demote", "gesv_mixed::factor",
         "gesv_mixed::solve0", "gesv_mixed::refine", VERDICT,
         "getrf", "getrf::prep", "getrf::panel", "getrf::pivots",
         "getrf::update", "getrf::reorder", "getrf::info")
#: the compiled programs of the lo factor (`lu._getrf_carry(lo=True)`)
#: and of the first lo solve and the refinement (`refine.py`), as the
#: device's `XLA Modules` line names them
FACTOR = ("jit__carry_panel_lo", "jit__carry_swap", "jit__carry_update",
          "jit__carry_finish")
REFINE = ("jit__ir_solve0", "jit__ir_sweeps")


def host_events(pd):
    """The path's spans in a loaded profile's host planes."""
    return hostspans.host_events(pd, SPANS + (FALLBACK,))


def slice_of(pd):
    return uploadtrace.UploadSlice(hostspans.device_ops(pd),
                                   host_events(pd),
                                   hostspans.clock_offset_ns(pd), ROOT)


def busy_by_step(ordered):
    """{"factor", "refine", "all"}: device seconds of the lo factor's
    programs, of the lo solve's and the refinement's, and of all
    programs, from `svdtrace.launches`. None where none of the path's
    programs ran."""
    step = {"factor": sum(s for _, name, s in ordered if name in FACTOR),
            "refine": sum(s for _, name, s in ordered if name in REFINE),
            "all": sum(s for _, _, s in ordered)}
    return step if step["factor"] and step["refine"] else None


_loaded = {}                        # xplane path -> (mtime, slice, steps)


def read(path):
    pd = reduce_trace.load(path)
    return slice_of(pd), busy_by_step(svdtrace.launches(pd))


def load(run):
    """(slice, `busy_by_step`) of the xplane the traced run left under
    `.bench_trace`, once per process; None without a device trace or
    the path's root span in it."""
    if not run.get("trace"):
        return None
    path = Tracer(os.path.join(hostspans.ROOT, ".bench_trace")).xplane()
    if path is None:
        return None
    mtime = os.path.getmtime(path)
    if _loaded.get(path, (None,))[0] != mtime:
        _loaded[path] = (mtime,) + read(path)
    _, sl, steps = _loaded[path]
    return (sl, steps) if sl.spans.get(ROOT) else None


def idle_verdict_share(run):
    """Percent of the device's idle time in the traced solve during
    which the host sat in its one read (`gesv_mixed::verdict`)."""
    got = load(run)
    if got is None or not got[0].idle_ns:
        return None
    return got[0].cover((VERDICT,))


def _busy_share(run, step):
    got = load(run)
    if got is None or got[1] is None or not got[1]["all"]:
        return None
    return 100.0 * got[1][step] / got[1]["all"]


def factor_busy_share(run):
    """Device seconds of the lo factor's programs over those of all
    programs in the traced solve, percent."""
    return _busy_share(run, "factor")


def refine_busy_share(run):
    """Device seconds of the first lo solve and the refinement over
    those of all programs in the traced solve, percent."""
    return _busy_share(run, "refine")


def _peak(run):
    from . import peaks
    return peaks.peak(run["device_kind"])


def solve_roofline(run):
    """The least time the chip could take for one solve (HPL-MxP's
    flops over the ONE-PASS bf16 peak, or the bytes a solve must move
    over the HBM peak, lib/mixedcount.py) over the device-busy seconds
    per solve in the traced slice, percent."""
    from . import mixedcount
    t, k = run["trace"], run["records"].get("slice_solves")
    cfg = run["config"]
    count = mixedcount.COUNTS.get(cfg.get("routine"))
    if not t or not k or count is None or not t["busy_s"]:
        return None
    peak = _peak(run)
    flops, nbytes = count(cfg["n"], cfg["nrhs"])
    least = max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
    return 100.0 * least / (t["busy_s"] / k)


def factor_roofline(run):
    """2/3 n^3 flops over the one-pass bf16 peak, over the device
    seconds of the lo factor's programs in the traced solve, percent:
    the new kernel's share."""
    from . import mixedcount
    got = load(run)
    k = run["records"].get("slice_solves")
    if got is None or got[1] is None or not k:
        return None
    least = mixedcount.factor(run["config"]["n"]) / _peak(run)["flops_per_s"]
    return 100.0 * least / (got[1]["factor"] / k)


def refine_sweeps_per_solve(run):
    """`refine.ir.iters` (the histogram's total: sweeps) over
    `refine.ir.calls`, counted over the whole window."""
    calls = run["counters"].get("refine.ir.calls")
    sweeps = run["histograms"].get("refine.ir.iters")
    if not calls or not sweeps:
        return None
    return sweeps["total"] / calls
