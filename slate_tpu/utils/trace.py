"""Tracing / phase timers (reference auxiliary/Trace.hh:98-108 RAII
events; per-phase timer map returned in opts, heev.cc:108).

A thin view over the unified event bus (slate_tpu/obs/events.py):
`on()`/`off()` toggle the bus, `block`/`mark` publish spans/instants
into it. The timeline is the Perfetto JSON (obs/export.py:
chrome_trace / write_trace) or, under a profiler session, the
profiler's own trace, where every bus span is an annotation on the
device's clock. The reference's SVG view (Trace.cc:359-627) is gone.
"""

from __future__ import annotations

import contextlib
import time

from ..obs import events as _bus


def on() -> None:
    """Reference trace::Trace::on() — enables the shared bus."""
    _bus.enable()


def off() -> None:
    """Disables the SHARED bus (one process-wide flag, ISSUE 3): a
    concurrently enabled obs session (bench --obs, tester
    --trace-out) stops collecting too."""
    _bus.disable()


def block(name: str):
    """RAII-style trace event (reference trace::Block), published to
    the bus under cat 'trace' (one span implementation lives in
    obs/events.py; this is a category-tagged view of it)."""
    return _bus.span(name, cat="trace")


def mark(name: str) -> None:
    """Zero-length event: a point-in-time annotation on the timeline
    (tune/select.py logs every autotuned decision through this, so
    decisions appear alongside the phase blocks they influenced)."""
    _bus.publish(name, _bus.PH_INSTANT, cat="tune")


class Timers:
    """Named-phase timer map (reference opts timers, heev.cc:108).
    Each phase also lands on the bus (cat 'phase') when it is on, so
    opts-timed driver phases show up in the Perfetto export."""

    def __init__(self) -> None:
        self.values = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            self.values[name] = self.values.get(name, 0.0) + t1 - t0
            _bus.publish(name, _bus.PH_SPAN, t0, t1, cat="phase")

    def __getitem__(self, k: str) -> float:
        return self.values[k]

    def __repr__(self) -> str:
        return "Timers(" + ", ".join(
            f"{k}={v:.4f}s" for k, v in self.values.items()) + ")"


def phases(opts):
    """Driver hook: returns a phase-context factory — `Timers.phase`
    when the caller passed an Option.Timers instance, a bus-only span
    factory when the bus is on (so instrumented drivers publish their
    phases with NO options plumbing), and a no-op context otherwise.
    The disabled path costs one boolean check per phase (reference
    per-phase timers returned in opts, heev.cc:108)."""
    from ..core.options import Option, get_option
    tm = get_option(opts, Option.Timers, None)
    if tm is not None:
        return tm.phase

    def bus_phase(name):
        return _bus.span(name, cat="phase")
    return bus_phase
