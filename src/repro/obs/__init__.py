"""repro.obs — unified telemetry (DESIGN.md §15).

* ``scopes``  — the names of the round's phases, carried by the
  compiled program as ``jax.named_scope`` metadata (``SCOPES``).
* ``spans``   — the ``Telemetry`` handle: host spans, each also a
  profiler ``TraceAnnotation`` named ``repro.<span>``.
* ``metrics`` — counters/gauges/histograms with Prometheus/JSON export.
* ``export``  — Chrome-trace/Perfetto JSON of any recorded window.

CLI: ``python -m repro.obs {trace,scrape}``.

Import note: ``repro.core`` imports ``obs.scopes``, so this package
stays dependency-light (jax and the standard library only).
"""
from .metrics import (Counter, Gauge, Histogram,  # noqa: F401
                      MetricsRegistry, default_registry)
from .scopes import SCOPES  # noqa: F401
from .spans import Mark, Span, Telemetry  # noqa: F401

_LAZY = {
    "to_chrome_trace": "export", "validate_chrome_trace": "export",
    "save_trace": "export", "load_trace": "export",
}

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "default_registry", "Mark", "SCOPES", "Span", "Telemetry",
           *_LAZY]


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module 'repro.obs' has no attribute "
                             f"{name!r}")
    import importlib
    return getattr(importlib.import_module(f".{mod}", __name__), name)
