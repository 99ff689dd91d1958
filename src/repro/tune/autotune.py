"""Perf-model-driven autotuner for (s, b, layout, approx)
(DESIGN.md §10).

The paper's experiments show the optimal s-step depth is machine- and
problem-dependent (its Section 5.2.1 tunes s offline from the Hockney
model); block size b, partition layout, and the kernel representation
interact with it — a deep s is free when rounds are latency-bound and
ruinous when the O((sb)^2) correction term or the m x sb KMV working
set dominates.  ``resolve_options`` turns ``SolverOptions`` knobs left
at ``"auto"`` into concrete choices:

  1. enumerate the candidate grid over exactly the auto knobs (pinned
     knobs are respected verbatim);
  2. drop infeasible points — s*b whose slab working-set bound
     (``perf_model.slab_fits_hbm``, same constraint ``best_s`` enforces)
     exceeds the HBM budget, b > m, s > max_iters;
  3. price every survivor with ``perf_model.modeled_fit_cost`` (exact
     rounds at data width, low-rank rounds at landmark width plus the
     one-time ``lowrank_setup_cost``) at the layout's processor count;
  4. optionally REFINE by measurement (``options.probe > 0``): the top
     modeled candidates each run ``probe`` outer rounds through the
     real solver and the fastest measured one wins — the model ranks,
     the machine decides.

The chosen plan is returned as a ``TunedPlan`` (resolved options +
modeled cost breakdown + the full searched frontier) and lands on
``FitResult.plan``, so a tuned fit documents why its configuration was
picked.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Tuple

import jax

from repro.core.perf_model import (Machine, choose_chunk_rows,
                                   device_memory_bytes, modeled_fit_cost,
                                   slab_fits_hbm)

S_CANDIDATES = (1, 2, 4, 8, 16, 32, 64, 128, 256)
B_CANDIDATES = (1, 2, 4, 8, 16, 32, 64)
PROBE_TOP_K = 3


@dataclasses.dataclass(frozen=True)
class TunedPlan:
    """What the autotuner decided and why: ``options`` has every knob
    concrete; ``modeled`` is the winner's ``modeled_fit_cost`` breakdown;
    ``frontier`` records every candidate searched (config, modeled time,
    feasibility) — infeasible points keep their modeled time so the
    frontier shows what the memory ceiling cost; ``probed`` the measured
    refinement rows when ``probe > 0`` ran."""

    options: object                # resolved SolverOptions
    modeled: dict
    frontier: Tuple[dict, ...]
    probed: Optional[Tuple[dict, ...]] = None

    @property
    def choice(self) -> dict:
        o = self.options
        return {"s": o.s, "b": o.b, "layout": o.layout, "approx": o.approx}


def _layout_P(layout: str, ndev: int) -> int:
    return 1 if layout == "serial" else max(ndev, 1)


def resolve_options(m: int, n: int, cfg, opts, *, problem: str = "krr",
                    A=None, y=None, mach: Machine = None,
                    hbm_bytes: int = None,
                    layouts=None) -> TunedPlan:
    """Resolve every ``"auto"`` knob of ``opts`` for an (m, n) problem
    (module docstring).  ``A``/``y`` enable the measured-probe
    refinement when ``opts.probe > 0``; without data the Hockney model
    decides alone.  ``layouts`` restricts the layout search space (the
    fleet solver passes its supported pair).  ``hbm_bytes`` defaults to
    the device's own memory budget (``perf_model.device_memory_bytes``)."""
    from repro.api import AUTO, LAYOUTS

    if not opts.needs_autotune:
        return TunedPlan(options=opts,
                         modeled=_price(m, n, cfg, opts, problem,
                                        opts.layout, mach),
                         frontier=())
    ndev = len(jax.devices())
    if hbm_bytes is None:
        hbm_bytes = device_memory_bytes()

    if opts.method != "sstep":
        s_cands = (1,)
    elif opts.s == AUTO:
        s_cands = tuple(s for s in S_CANDIDATES if s <= opts.max_iters)
    else:
        s_cands = (opts.s,)
    if problem != "krr":
        b_cands = (1,)
    elif opts.b == AUTO:
        b_cands = tuple(b for b in B_CANDIDATES if b <= m)
    else:
        b_cands = (opts.b,)
    if opts.layout == AUTO:
        lay_cands = ("serial",) if ndev == 1 else ("serial", "1d", "2d")
        if layouts is not None:
            lay_cands = tuple(l for l in lay_cands if l in layouts)
        # the 2d layout shards samples: it needs m divisible by the
        # data-axis extent (the facade's auto mesh uses every device)
        lay_cands = tuple(l for l in lay_cands
                          if l != "2d" or m % max(ndev, 1) == 0)
    else:
        lay_cands = (opts.layout,)
    if opts.stream is not None:
        # the streamed representation is serial + exact by construction
        # (SolverOptions validates the pinned combinations; here the
        # remaining AUTO dimensions are restricted to the compatible
        # subspace)
        lay_cands = ("serial",)
    assert all(l in LAYOUTS for l in lay_cands)
    if opts.approx == AUTO:
        # a rank >= m "approximation" is strictly more work than exact
        ap_cands = ((None, "nystrom") if opts.landmarks < m else (None,))
    else:
        ap_cands = (opts.approx,)
    if opts.stream is not None:
        ap_cands = (None,)

    frontier = []
    for lay in lay_cands:
        P = _layout_P(lay, ndev)
        for ap in ap_cands:
            l = min(opts.landmarks, m)
            for b in b_cands:
                for s in s_cands:
                    # KMV working-set bound: identical constraint to
                    # perf_model.best_s (s=1 is the classical floor).
                    # Streamed runs have no m-tall working set at all —
                    # that ceiling is exactly what streaming removes.
                    feasible = (opts.stream is not None or s == 1
                                or slab_fits_hbm(m, s * b, hbm_bytes))
                    cost = modeled_fit_cost(
                        m, n, cfg.kernel.name, b=b, s=s,
                        iters=opts.max_iters, P=P, mach=mach,
                        approx=ap, landmarks=l)
                    frontier.append({"s": s, "b": b, "layout": lay,
                                     "approx": ap, "time": cost["time"],
                                     "feasible": feasible})
    feas = [f for f in frontier if f["feasible"]]
    if not feas:
        # only reachable when s (and/or b) is PINNED above the HBM
        # working-set budget — s="auto" always carries the s=1 floor.
        # The tuner must not silently override a pinned knob, so the
        # remaining auto dimensions are resolved best-effort toward the
        # smallest working set instead of crashing.
        feas = sorted(frontier,
                      key=lambda f: (f["s"] * f["b"], f["time"]))
    else:
        feas.sort(key=lambda f: (f["time"], f["s"], f["b"]))

    probed = None
    if opts.probe > 0 and A is not None and y is not None:
        probed = _probe(A, y, cfg, opts, problem, feas[:PROBE_TOP_K])
        winner = min(probed, key=lambda p: p["measured_s"])
    else:
        winner = feas[0]

    resolved = dataclasses.replace(
        opts, s=winner["s"], b=winner["b"], layout=winner["layout"],
        approx=winner["approx"])
    if resolved.stream == AUTO:
        # chunk_rows="auto": best modeled streaming-pipeline time whose
        # double-buffered working set fits the on-chip budget, at the
        # winner's (s, b) slab width (DESIGN.md §14)
        resolved = dataclasses.replace(resolved, stream=choose_chunk_rows(
            m, n, winner["s"] * winner["b"], cfg.kernel.name, mach=mach))
    if resolved.guard and resolved.recompute_every == AUTO:
        # price drift correction for the WINNER (s, b, layout): the
        # cadence that keeps guarded overhead under the budget.  The
        # distributed layouts recompute from alpha every round — no
        # drifting residual, correction off (see repro.resilience).
        if winner["layout"] == "serial":
            from repro.core.perf_model import choose_recompute_every
            rec = choose_recompute_every(
                m, n, cfg.kernel.name,
                b=winner["b"] if problem == "krr" else 1,
                s=winner["s"], mach=mach,
                approx=bool(winner["approx"]),
                landmarks=min(opts.landmarks, m) if winner["approx"]
                else 0)
        else:
            rec = 0
        resolved = dataclasses.replace(resolved, recompute_every=rec)
    return TunedPlan(options=resolved,
                     modeled=_price(m, n, cfg, resolved, problem,
                                    winner["layout"], mach),
                     frontier=tuple(frontier),
                     probed=None if probed is None else tuple(probed))


def _price(m, n, cfg, opts, problem, layout, mach):
    ndev = len(jax.devices())
    s = opts.s_eff if opts.s != "auto" or opts.method != "sstep" else 1
    b = opts.b if (problem == "krr" and isinstance(opts.b, int)) else 1
    l = min(opts.landmarks, m) if opts.approx else 0
    return modeled_fit_cost(m, n, cfg.kernel.name, b=b, s=s,
                            iters=opts.max_iters,
                            P=_layout_P(layout, ndev), mach=mach,
                            approx=opts.approx, landmarks=l)


def _probe(A, y, cfg, opts, problem, candidates):
    """Measured refinement: run ``opts.probe`` outer rounds of each top
    candidate through the real facade solver (budget stopping, no
    metric) twice — the first call pays compile, the second is the
    measurement — and report wall seconds.

    Probe fits run with ``telemetry=None`` — their spans/marks belong
    to the tuner, not the fit being tuned; the PARENT handle (when the
    tuned fit carries one) records each probe as a counter bump and a
    wall-seconds histogram sample instead."""
    from repro.api import _fit, _active_tel

    from repro.api import AUTO

    tel = _active_tel(opts)
    rows = []
    for cand in candidates:
        s_eff = cand["s"] if opts.method == "sstep" else 1
        stream = opts.stream
        if stream == AUTO:               # concretize per candidate so the
            m, n = A.shape               # probe fit needs no re-tuning
            stream = choose_chunk_rows(m, n, cand["s"] * cand["b"],
                                       cfg.kernel.name)
        probe_opts = dataclasses.replace(
            opts, s=cand["s"], b=cand["b"], layout=cand["layout"],
            approx=cand["approx"], tol=0.0, record=False, probe=0,
            stream=stream, max_iters=max(opts.probe * s_eff, 1),
            telemetry=None)
        _fit(problem, A, y, cfg, probe_opts)         # compile + warm
        t0 = time.perf_counter()
        _fit(problem, A, y, cfg, probe_opts)
        dt = time.perf_counter() - t0
        rows.append(dict(cand, measured_s=dt))
        if tel is not None:
            tel.metrics.counter(
                "repro_autotune_probes_total",
                "measured autotune probes run").inc(
                    layout=cand["layout"])
            tel.metrics.histogram(
                "repro_autotune_probe_seconds",
                "measured wall seconds per probe fit").observe(dt)
    return rows
