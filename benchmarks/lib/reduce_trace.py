"""From the profiler's xplane to the few numbers the per-layer metrics
read: device-busy union, module launches, the operations that took
most time, the longest idle gaps. Read with nothing but JAX
(`jax.profiler.ProfileData`).

What a TPU trace holds (looked at by hand, PR 24): one plane per chip
named `/device:TPU:<i>`; on it a line `XLA Modules` with one event per
execution of a compiled program, and a line `XLA Ops` with one event
per HLO operation executed, nested where an operation (a `while`, a
fusion's call) contains others. Busy is the union of the `XLA Ops`
intervals; an operation's time in the top list is its SELF time, so a
loop does not count its body twice.
"""

MODULES = "XLA Modules"
OPS = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
TOP = 10


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def short_name(name):
    """`%copy.4 = f32[16384,16384]{1,0:T(8,128)} copy(...)` ->
    `%copy.4 copy f32[16384,16384]`: an operation's name, opcode and
    result shape, without layouts and operands."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name[:100]
    if rest.startswith("("):                # a tuple of results
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, after = "(tuple)", rest[i + 1:].lstrip()
    else:
        shape, _, after = rest.partition(" ")
        shape = shape.split("{", 1)[0]
    return ("%s %s %s" % (head, after.split("(", 1)[0], shape))[:100]


def _events(line, rename=lambda s: s):
    return [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
             rename(e.name)) for e in line.events]


def union_ns(intervals):
    """Total length and the merged pieces of a set of (start, end)."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def self_times(events):
    """name -> seconds not covered by the operation's own children, for
    properly nested (start, end, name) events of one line."""
    out = {}
    stack = []                      # [end, name, duration, child_total]

    def close(item):
        out[item[1]] = out.get(item[1], 0.0) + max(item[2] - item[3], 0.0)

    for s, e, name in sorted(events, key=lambda t: (t[0], -(t[1] - t[0]))):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([e, name, e - s, 0.0])
    while stack:
        close(stack.pop())
    return {k: v / 1e9 for k, v in out.items()}


def _top(d):
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
            [:TOP]]


def reduce_planes(planes, window_s):
    """`planes`: [(plane name, {line name: [(start, end, name)]})].
    Averages busy time over the device planes that ran anything."""
    busy, launches, ops, gaps = [], 0, {}, {}
    for pname, lines in planes:
        if not pname.startswith(DEVICE_PREFIX):
            continue
        mods = lines.get(MODULES, [])
        evs = lines.get(OPS) or mods
        if not evs:
            continue
        total, merged = union_ns([(s, e) for s, e, _ in evs])
        busy.append(total / 1e9)
        launches += len(mods)
        for name, sec in self_times(evs).items():
            ops[name] = ops.get(name, 0.0) + sec
        # an idle gap is named by the program that ended it: the one
        # whose launch the device was waiting for
        starts = sorted((s, name) for s, _, name in mods)
        j = 0
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            while j < len(starts) and starts[j][0] < s1:
                j += 1
            name = "before " + (starts[j][1] if j < len(starts)
                                else "(no later launch)")
            gaps[name] = gaps.get(name, 0.0) + (s1 - e0) / 1e9
    if not busy:
        return None
    n = len(busy)
    return {"busy_s": sum(busy) / n, "window_s": float(window_s),
            "planes": n, "module_launches": launches,
            "device_ops": _top(ops), "idle_gaps": _top(gaps)}


def idle_percent(reduced):
    """1 - busy / traced slice, in percent (None without a trace)."""
    if not reduced or not reduced["window_s"]:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def reduce_file(path, window_s):
    pd = load(path)
    return reduce_planes(
        [(p.name, {ln.name: _events(ln, short_name if ln.name == OPS
                                    else lambda s: s.split("(")[0])
                   for ln in p.lines if ln.name in (MODULES, OPS)})
         for p in pd.planes if p.name.startswith(DEVICE_PREFIX)],
        window_s)


def describe(path, limit=6):
    """Plane and line names with their event counts and first events:
    what to look at by hand before trusting the reduction."""
    out = []
    for p in load(path).planes:
        for ln in p.lines:
            evs = list(ln.events)
            out.append({"plane": p.name, "line": ln.name, "events": len(evs),
                        "first": [[e.name[:80], float(e.start_ns),
                                   float(e.duration_ns)]
                                  for e in evs[:limit]]})
    return out
