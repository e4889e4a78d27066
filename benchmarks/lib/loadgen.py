"""Open-loop load from one sender thread. Requests are due on a
schedule made from the seed whatever the system does; each is timed
from when it was DUE, so a stall charges the requests behind it, and
how late the sender ran is reported beside the latencies.

Completion is seen by polling `done()` from the caller's thread and
never by blocking in `result()`: in this program a blocking
`Ticket.result()` force-flushes its own bucket (batch/queue.py), which
would turn every request into a dispatch of one and measure a tier
without its coalescing window. A poll sweep every POLL_S stamps a
completion at most that late.

Every run also keeps what names a stall afterwards (`longest_stall`):
the process's CPU seconds sampled every CLOCK_S, and each garbage
collection's start and length.
"""

import collections
import gc
import threading
import time

POLL_S = 0.0002
CLOCK_S = 0.02


def percentile(values, q):
    """The q-th percentile by rank (the smallest value with at least q%
    of all values at or under it)."""
    s = sorted(values)
    return s[min(len(s) - 1, max(-(-q * len(s) // 100) - 1, 0))]


def schedule(r, count, rate):
    """Due times (s from window start) of `count` arrivals at `rate`/s:
    the stratified exponential gaps, shuffled by the seed."""
    import numpy as np
    from . import gen
    gaps = np.asarray(gen.exponential_gaps(count, rate))
    r.shuffle(gaps)
    return np.cumsum(gaps)


class OpenLoop:
    """`send(i)` returns a handle with `done()`, or raises; `hooks` are
    (due_s, callable) pairs the polling thread runs once their time has
    come (the traced run starts its profiler slice this way)."""

    def __init__(self, due, send, grace_s=30.0, hooks=()):
        self.due = [float(d) for d in due]
        self.send = send
        self.grace_s = grace_s
        self.hooks = sorted(hooks, key=lambda h: h[0])
        n = len(self.due)
        self.sent = [None] * n          # s from window start
        self.finished = [None] * n
        self.handles = [None] * n
        self.errors = [None] * n
        self.clock = []                 # (s from start, process CPU s)
        self.collections = []           # (s from start, seconds)
        self._sent = collections.deque()    # sender -> poller, indices

    def _sender(self, t0):
        for i, d in enumerate(self.due):
            while True:
                left = t0 + d - time.perf_counter()
                if left <= 0:
                    break
                time.sleep(left if left < 0.001 else left - 0.0005)
            self.sent[i] = time.perf_counter() - t0
            try:
                h = self.send(i)
            except Exception as e:      # refused or failed at the door
                self.errors[i] = e
                continue
            self.handles[i] = h
            self._sent.append(i)

    def run(self):
        """Returns the seconds the last due time lies after the start
        (the window); fills sent/finished/errors."""
        t0 = time.perf_counter()
        began = []

        def on_gc(phase, info):
            if phase == "start":
                began.append(time.perf_counter())
            elif began:
                b = began.pop()
                self.collections.append((b - t0, time.perf_counter() - b))

        gc.callbacks.append(on_gc)
        try:
            return self._run(t0)
        finally:
            gc.callbacks.remove(on_gc)

    def _run(self, t0):
        th = threading.Thread(target=self._sender, args=(t0,),
                              name="loadgen-sender", daemon=True)
        th.start()
        window = self.due[-1] if self.due else 0.0
        hooks = list(self.hooks)
        open_ = []
        while True:
            now = time.perf_counter() - t0
            if not self.clock or now - self.clock[-1][0] >= CLOCK_S:
                self.clock.append((now, time.process_time()))
            while hooks and now >= hooks[0][0]:
                hooks.pop(0)[1]()
            alive = th.is_alive()
            while self._sent:
                open_.append(self._sent.popleft())
            still = []
            for i in open_:
                if self.handles[i].done():
                    self.finished[i] = time.perf_counter() - t0
                else:
                    still.append(i)
            open_ = still
            if (not alive and not open_) or now > window + self.grace_s:
                break
            time.sleep(POLL_S)
        th.join(timeout=self.grace_s)
        while hooks:
            hooks.pop(0)[1]()
        return window


def longest_stall(loop):
    """The longest time with requests open and none completing, and
    what the process did meanwhile, so that a stall names its side:
    `sender_late_s` (the worst sent - due among requests due inside it)
    near `gap_s` means the whole process stood still (the host took its
    cores, or one thread held the interpreter); a sender on time means
    the server or the device did. `cpu_s` is the CPU the process burnt
    over all its threads between the clock samples around the gap,
    `gc_s` the garbage collections that began inside it, `open` and
    `open_at_end` the requests outstanding at its two ends."""
    pairs = sorted((f, s) for f, s in zip(loop.finished, loop.sent)
                   if f is not None and s is not None)
    if len(pairs) < 2:
        return None
    # before each completion, the wait since the one before it or since
    # the oldest request then open was sent, whichever came later
    oldest = min((s for f, s in zip(loop.finished, loop.sent)
                  if f is None and s is not None), default=float("inf"))
    waits = []
    for k in range(len(pairs) - 1, 0, -1):
        oldest = min(oldest, pairs[k][1])
        start = max(pairs[k - 1][0], oldest)
        waits.append((pairs[k][0] - start, start))
    gap, at = max(waits)
    end = at + gap
    late = [s - d for s, d in zip(loop.sent, loop.due)
            if s is not None and at <= d <= end]
    before = [c for t, c in loop.clock if t <= at]
    after = [c for t, c in loop.clock if t >= end]
    def open_at(t):
        return sum(1 for s, f in zip(loop.sent, loop.finished)
                   if s is not None and s <= t and (f is None or f >= t))

    return {"gap_s": gap, "at_s": at, "open": open_at(at),
            "open_at_end": open_at(end),
            "sender_late_s": max(late, default=0.0),
            "cpu_s": after[0] - before[-1] if before and after else None,
            "gc_s": sum(d for t, d in loop.collections if at <= t <= end)}
