"""The clock between an xplane's host planes and its device planes,
bracketed from both sides (PR 36).

`hostspans.clock_offset_ns` takes the smallest enqueue-to-execution
lag for (device clock - host clock): an upper bound, met only while
the device waits for the host. Once the host runs ahead (the first
program of an in-core solve waits 28 ms for A's transfer) every lag is
long and the bound read 34.2 ms too high in one traced run (PERF.md,
PR 30). The program's clock beacon cures it: with the obs bus on, an
upload of 16 MiB or more through the matrix constructors is preceded
by the one-element program `obs_clock_sync`, launched and waited for
inside the span `obs::clock_sync` by the constructing thread just
before the hand-over, so that it runs ahead of every program that
waits for the upload (slate_tpu/obs/events.py `clock_beacon`). The
execution starts after the runtime began to enqueue it (the host
event `DoEnqueueProgram` of the same `run_id`, inside the span) and
ends before the span closes, so each beacon holds the offset between

    below = execution end - span end         (device - host, too low
                                              by what the runtime takes
                                              to acknowledge a program,
                                              0.45 ms on the v5e, and
                                              the waiter's wake-up)
    above = execution start - enqueue start  (too high by the launch
                                              latency, tens of us)

and the offset is taken at the bracket's middle.

Nothing here edits or replaces `hostspans`: the metrics that PR 25-33
defined stay on the one-sided clock until a `benchmark` issue points
them here.
"""

from . import hostspans, reduce_trace

BEACON_SPAN = "obs::clock_sync"
BEACON_PROGRAM = "jit_obs_clock_sync"


def beacons(pd):
    """[(below, above)] per beacon of a loaded profile, nanoseconds:
    the `obs::clock_sync` spans of the host planes and the executions
    of `jit_obs_clock_sync` on the device planes (`XLA Modules`), each
    in order of start and paired in that order; `above` from the
    execution's own `DoEnqueueProgram` (by `run_id`) where it lies
    inside the span, else from the span's start. [] where there is
    none, or the two counts differ (a span open when the profiler
    started or stopped is not in the xplane)."""
    spans = sorted(e[:2] for e in hostspans.host_events(pd, {BEACON_SPAN}))
    runs = sorted(
        (float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
         dict(e.stats).get("run_id"))
        for p in pd.planes if p.name.startswith(reduce_trace.DEVICE_PREFIX)
        for ln in p.lines if ln.name == reduce_trace.MODULES
        for e in ln.events if e.name.split("(")[0] == BEACON_PROGRAM)
    if not spans or len(spans) != len(runs):
        return []
    enqueued = [(args.get("run_id"), s) for s, _, _, args
                in hostspans.host_events(pd, {hostspans.ENQUEUE})]
    out = []
    for (s0, s1), (r0, r1, rid) in zip(spans, runs):
        # the launch's earliest enqueue event inside the span
        at = min((s for k, s in enqueued
                  if rid is not None and k == rid and s0 <= s <= s1),
                 default=s0)
        out.append((r1 - s1, r0 - at))
    return out


def bracket_ns(pd):
    """(below, above) on (device clock - host clock) of a loaded
    profile, nanoseconds. `above` is what `hostspans.clock_offset_ns`
    computes, tightened by every beacon's (execution start - enqueue
    start); `below` the largest (execution end - span end) over the
    beacons. None where the xplane holds no beacon."""
    got = beacons(pd)
    if not got:
        return None
    above = min(a for _, a in got)
    if hostspans.host_events(pd, {hostspans.ENQUEUE}):
        above = min(above, hostspans.clock_offset_ns(pd))
    return max(b for b, _ in got), above


def offset_ns(bracket):
    """The offset to move the device's intervals by, of a `bracket_ns`
    result: its midpoint, off by half its width at most (the bracket
    lies inside every single beacon's own, the tightest one's too).
    None of None: a metric that needs the two-sided clock is then left
    out, not computed on the one-sided one."""
    if bracket is None:
        return None
    return (bracket[0] + bracket[1]) / 2.0
