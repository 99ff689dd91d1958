"""Span recorder for solves and serving windows (DESIGN.md §15).

One ``Telemetry`` handle records two kinds of host observation:

  * SPANS — ``with tel.span("representation_build", "setup"):`` —
    intervals on ``time.perf_counter`` around host-side phases (the
    fit, its schedule draw and representation build, the solve,
    guarded segments, engine steps).  Each span also opens a
    ``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so a
    profile taken around the work shows the program's phases on the
    profiler's own clock, beside the device's operations.
  * MARKS — ``tel.mark("fallback", "guard")`` — instant host events.

Inside jitted code the handle records nothing: the round's phases are
named in the compiled program by ``jax.named_scope`` (``obs.scopes``),
which is op metadata, so a fit compiles the same program with telemetry
on or off.

A span that brackets device work must end with a ``block_until_ready``
on the phase's output, or it times the dispatch rather than the work.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.monitoring


@dataclasses.dataclass
class Span:
    """One closed host interval: ``[t0, t1]`` on ``time.perf_counter``'s
    clock, tagged with a phase (setup/solve/serve/fit/...) and free-form
    args."""

    name: str
    phase: str
    t0: float
    t1: float
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def duration(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Mark:
    """One instantaneous host event."""

    name: str
    phase: str
    t: float
    value: Optional[float] = None


_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def programs_built(out: Dict[str, Any]):
    """Count into ``out["compiles"]`` the XLA programs the calling thread
    builds while the with-body runs: ``jax.monitoring``'s backend-compile
    event, which fires once per program compiled or loaded from the
    persistent cache, and never for a program jit already holds."""
    tid = threading.get_ident()
    out["compiles"] = 0

    def on_event(event, _secs, **_):
        if event == _BACKEND_COMPILE and threading.get_ident() == tid:
            out["compiles"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield out
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


class Telemetry:
    """The recording handle ``SolverOptions(telemetry=...)`` and
    ``ServingEngine(telemetry=...)`` accept (DESIGN.md §15).

    Holds the span/mark log plus a ``MetricsRegistry``
    (counters/gauges/histograms — obs/metrics.py).  ``enabled=False``
    makes every recording call a no-op, and opens no annotation.
    """

    def __init__(self, *, enabled: bool = True, metrics=None):
        from .metrics import MetricsRegistry
        self.enabled = bool(enabled)
        self.spans: List[Span] = []
        self.marks: List[Mark] = []
        self.metrics = MetricsRegistry() if metrics is None else metrics

    @contextlib.contextmanager
    def span(self, name: str, phase: str = "host", *,
             count_compiles: bool = False, **args):
        """Record a closed host span around the with-body, inside a
        profiler annotation ``repro.<name>`` (no-op when disabled).
        The span is appended at EXIT, so the log stays ordered by end
        time.  ``count_compiles=True`` adds the arg ``compiles``: the
        XLA programs this thread built in the body (``programs_built``)."""
        if not self.enabled:
            yield None
            return
        built = (programs_built(args) if count_compiles
                 else contextlib.nullcontext())
        with jax.profiler.TraceAnnotation(f"repro.{name}"), built:
            t0 = time.perf_counter()
            try:
                yield None
            finally:
                self.spans.append(Span(name, phase, t0,
                                       time.perf_counter(), args))

    def mark(self, name: str, phase: str = "host", value=None) -> None:
        """Record one instant event (no-op when disabled)."""
        if not self.enabled:
            return
        self.marks.append(Mark(name, phase, time.perf_counter(),
                               None if value is None else float(value)))

    def window(self):
        """(t_min, t_max) over everything recorded, or None when empty."""
        ts = [s.t0 for s in self.spans] + [m.t for m in self.marks]
        te = [s.t1 for s in self.spans] + [m.t for m in self.marks]
        if not ts:
            return None
        return min(ts), max(te)

    def clear(self) -> None:
        """Drop every recorded span/mark (metrics are kept — counters
        are cumulative by design)."""
        self.spans.clear()
        self.marks.clear()
