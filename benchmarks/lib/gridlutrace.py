"""The traced solve of the pivoted LU on a grid (kinds/gridlu.py): what
the per-layer metrics `gridlu.*` read beside lib/gridtrace.py's, which
serve any routine (collectives, imbalance, launches, the whole solve's
roofline) and are imported as they are. gridtrace.py's slice is bounded
by `posv`'s driver spans and could not be edited by the PR that added
this kind, so the slice here is lib/uploadtrace.py's under `gesv`'s.

The factorization is ONE program a solve (`lu._lu_scan_grid` inside
`jit_factor`), so its parts are told apart on a chip's `XLA Ops` line
by where an operation sits and by what it holds, not by a program's
name (the line carries the HLO text of each operation and no scope):

    panel     a `while` nested inside another `while` whose text holds
              an array nb wide (the stage loop runs the panel kernel's
              block loop, `lu.lu_panel_blocked`, whose carry is the
              (m, nb) panel), and everything under it;
    exchange  outside a panel, an operation whose text holds an array
              of 2 nb rows: the gather of the rows a step exchanges,
              their all-reduce along `p` and their scatter
              (`blocked._exchange_rows`; nothing else in the program
              is 2 nb tall);
    the rest  the updates, the blocks taken and put, the stage moves.

An operation's time is its SELF time (`reduce_trace.self_times`' rule),
so a loop does not count its body twice. Everything returns None where
the run has no device trace (a rehearsal on the CPU) or nothing of the
kind ran (a program before PR 49 cannot run the cell at all).
"""

import os

from . import gridtrace, hostspans, reduce_trace, uploadtrace
from .tracer import Tracer

#: driver spans open for a whole call: they bound the slice, and cover
#: no idle time
ROOTS = ("gesv", "getrf")
#: every span of the path (tier-1 looks for each in a rehearsal)
SPANS = ROOTS + ("grid::place", "matrix::h2d", "getrf::prep",
                 "getrf::grid_factor", "getrs::grid_solve")
PHASES = ("panel", "exchange", "collective", "rest")


def host_events(pd):
    """The path's spans in a loaded profile's host planes."""
    return hostspans.host_events(pd, SPANS)


def slice_of(planes, spans, offset_ns=0.0):
    """The solve's slice: `uploadtrace.UploadSlice` under `gesv`'s
    root, from the first `matrix::h2d` (which opens with the first
    `grid::place`, before any driver span: the upload is the chips'
    idle time) to the end of the root span; per chip the idle pieces
    on the host's clock, and the host spans by name."""
    return uploadtrace.UploadSlice(planes, spans, offset_ns, ROOTS[0])


def phases(events, nb, table=None):
    """Seconds of one chip's `XLA Ops` events [(start ns, end ns, HLO
    text)] by PHASES, self times. `table`, a dict, receives
    (phase, operation's short name) -> [seconds, count]."""
    rows, wide = "[%d," % (2 * nb), ",%d]" % nb
    out = dict.fromkeys(PHASES, 0.0)
    stack = []          # [end, phase, in a while, duration, children, name]

    def close(item):
        sec = max(item[3] - item[4], 0.0) / 1e9
        out[item[1]] += sec
        if table is not None:
            cell = table.setdefault(
                (item[1], reduce_trace.short_name(item[5])), [0.0, 0])
            cell[0] += sec
            cell[1] += 1

    for s, e, name in sorted(events, key=lambda t: (t[0], t[0] - t[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        op = gridtrace.opcode(name)
        loop = op == "while"
        if stack and stack[-1][1] == "panel":
            phase = "panel"
        elif loop and stack and stack[-1][2] and wide in name:
            phase = "panel"
        elif rows in name and not loop:
            phase = "exchange"
        elif gridtrace.is_collective(op):
            phase = "collective"
        else:
            phase = "rest"
        if stack:
            stack[-1][4] += e - s
        stack.append([e, phase, loop or bool(stack and stack[-1][2]),
                      e - s, 0.0, name])
    while stack:
        close(stack.pop())
    return out


def read(pd, nb, table=None):
    """{"slice": `slice_of`, "phase_s": [per chip {phase: seconds}],
    "busy_s": [per chip]} of a loaded profile; None without a device
    plane that ran anything."""
    per_chip, busy = [], []
    for p in pd.planes:
        if not p.name.startswith(reduce_trace.DEVICE_PREFIX):
            continue
        line = next((ln for ln in p.lines if ln.name == reduce_trace.OPS),
                    None)
        evs = reduce_trace._events(line) if line is not None else []
        if not evs:
            continue
        busy.append(reduce_trace.union_ns([(s, e) for s, e, _ in evs])[0]
                    / 1e9)
        per_chip.append(phases(evs, nb, table))
    if not busy:
        return None
    sl = slice_of(hostspans.device_ops(pd), host_events(pd),
                  hostspans.clock_offset_ns(pd))
    return {"slice": sl, "phase_s": per_chip, "busy_s": busy}


_loaded = {}                        # xplane path -> (mtime, nb, read(pd))


def load(run):
    """`read` of the xplane the traced run left under `.bench_trace`,
    once per process; None without a device trace."""
    if not run.get("trace"):
        return None
    path = Tracer(os.path.join(hostspans.ROOT, ".bench_trace")).xplane()
    if path is None:
        return None
    key = (os.path.getmtime(path), run["config"]["mb"])
    if _loaded.get(path, (None,))[0] != key:
        _loaded[path] = (key, read(reduce_trace.load(path), key[1]))
    return _loaded[path][1]


def _mean(values):
    return sum(values) / len(values)


def phase_share(run, phase):
    """Device seconds of `phase` over device-busy seconds in the
    traced solve, percent, mean of the chips."""
    t = load(run)
    if t is None:
        return None
    shares = [100.0 * ph[phase] / b
              for ph, b in zip(t["phase_s"], t["busy_s"]) if b]
    return _mean(shares) if shares else None


def panel_roofline(run):
    """The least time ONE chip could take for the panel factorizations
    of a solve at the heights they ran (lib/gridlucount.py: every chip
    factors every panel whole, so the count and the peak are one
    chip's) over the panel's device seconds per solve, mean of the
    chips, percent."""
    from . import gridlucount, opcount, peaks
    t, k = load(run), run["records"].get("slice_solves")
    if t is None or not k:
        return None
    cfg = run["config"]
    panel = _mean([ph["panel"] for ph in t["phase_s"]]) / k
    rows = counter_per_solve(run, "grid.lu_panel_rows_factored")
    if not panel or not rows:
        return None
    least, _bound = opcount.roofline_seconds(
        *gridlucount.panel_factors(cfg["n"], cfg["mb"], rows),
        peaks.peak(run["device_kind"]))
    return 100.0 * least / panel


def idle_cover(run, names):
    """Percent of the chips' idle nanoseconds in the solve during which
    a span of `names` was open on some thread."""
    t = load(run)
    if t is None or not t["slice"].spans:
        return None
    return t["slice"].cover(names)


def counter_per_solve(run, name, scale=1.0):
    """Counter `name` over the window, per solve; None where the
    program counted nothing of the kind."""
    n = run["records"].get("solves")
    if not run["counters"].get(name) or not n:
        return None
    return run["counters"][name] / n / scale
