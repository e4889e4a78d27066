"""Readers that several per-layer metrics share. A quantity whose cells
report different end-to-end metrics is split into one metric per
group of cells (BENCHMARK.json: a metric moves ONE end-to-end metric);
each of those names is a file under layer_metrics/ that takes its
`compute` from here, so the arithmetic is written once."""

from . import opcount, peaks, reduce_trace


def idle_share(run):
    """1 - (union of the device-operation intervals) / (length of the
    traced slice), in percent."""
    return reduce_trace.idle_percent(run["trace"])


def launches_per_solve(run):
    """Executions of compiled programs on the device (events of the
    `XLA Modules` line) in the traced slice, per solve in it."""
    t, n = run["trace"], run["records"].get("slice_solves")
    if not t or not n:
        return None
    return t["module_launches"] / n


def solve_roofline(run):
    """The least time the chip could take for one solve (the larger of
    flops over the bf16 peak and bytes over the HBM peak, both from the
    shapes, lib/opcount.py) over the device-busy seconds per solve in
    the traced slice, in percent. The solves run in f32 at HIGHEST (six
    bf16 passes), so this sits well under 100% by construction."""
    t, n = run["trace"], run["records"].get("slice_solves")
    cfg = run["config"]
    count = opcount.COUNTS.get(cfg.get("routine"))
    if not t or not n or count is None or not t["busy_s"]:
        return None
    flops, nbytes = count(cfg["n"], cfg["nrhs"])
    least, _bound = opcount.roofline_seconds(
        flops, nbytes, peaks.peak(run["device_kind"]))
    return 100.0 * least / (t["busy_s"] / n)
