"""Static analysis for the repro tree (DESIGN.md §11).

Four analyzers behind one CLI (``python -m repro.analysis``):

* ``pallas_check`` — Pallas kernel sanitizer (races, coverage holes,
  tile alignment, VMEM budget, unexercised sites)
* ``lint`` — jit hygiene (tracer branching in round fns, unregistered
  pytree dataclasses, callable-typed static args)
* ``comm_check`` — s-step collective auditor (census of traced
  collectives vs ``perf_model``'s modeled message schedule)
* ``guard_check`` — guarded-carry coverage auditor (every floating
  carry leaf must be seen by the divergence-guard health predicate)

Findings carry stable check IDs and honor justified
``# repro: noqa[CHK-...]`` suppressions (``findings`` module).
"""
from .findings import (ERROR, INFO, WARNING, Finding,  # noqa: F401
                       apply_suppressions, render_report)

ANALYZERS = ("pallas", "lint", "comm", "guard")

CHECKS = {
    "CHK-RACE": ("pallas", "error",
                 "output block written from >1 parallel grid point"),
    "CHK-HOLE": ("pallas", "error", "output block never written"),
    "CHK-ALIGN": ("pallas", "warning",
                  "block shape off the dtype's (sublane, lane) tile"),
    "CHK-VMEM": ("pallas", "warning",
                 "double-buffered working set exceeds VMEM"),
    "CHK-SITE": ("pallas", "warning",
                 "pallas_call site not exercised by the registry"),
    "CHK-TRACER": ("lint", "error",
                   "host branching/coercion on traced value in round fn"),
    "CHK-PYTREE": ("lint", "error",
                   "array-carrying dataclass not a registered pytree"),
    "CHK-STATIC": ("lint", "info",
                   "Callable-typed static argname (retrace hazard)"),
    "CHK-COMM": ("comm", "error",
                 "collective executions != modeled message schedule"),
    "CHK-AXIS": ("comm", "error", "collective over unknown mesh axis"),
    "CHK-SSTEP": ("comm", "error",
                  "s-step per-round collectives != classical/s"),
    "CHK-CARRY": ("guard", "error",
                  "guarded-carry leaf missed by the health predicate"),
    "CHK-NOQA": ("-", "error", "suppression without justification"),
}


def run_all(only=None):
    """Run the selected analyzers (all by default) and resolve
    suppressions; returns the full finding list, suppressed included."""
    from . import comm_check, guard_check, lint, pallas_check
    runners = {"pallas": pallas_check.run, "lint": lint.run,
               "comm": comm_check.run, "guard": guard_check.run}
    selected = ANALYZERS if not only else tuple(only)
    found = []
    for name in selected:
        found.extend(runners[name]())
    return apply_suppressions(found)
