"""Figure 8 (beyond paper): the price of a guarded solve (DESIGN.md §12,
EXPERIMENTS.md §Resilience).

The resilience layer claims to be near-free: the residual recurrence
reuses the m x sb slab each round already evaluates, the health
predicate is O(m) elementwise, and the only real cost — the periodic
exact recompute ``f = K @ alpha`` — is amortized by the autotuned
``recompute_every`` cadence under ``perf_model.GUARD_OVERHEAD_BUDGET``.
This benchmark measures all three acceptance gates:

  * OVERHEAD — wall-clock of a guarded fit (autotuned cadence, sized so
    drift correction actually fires) vs the identical unguarded fit,
    both jit-warm; gate: measured overhead <= 10%.
  * RECOVERY — a NaN injected mid-solve; the guard discards the
    poisoned round and the ladder falls back; gate: final alpha within
    1e-5 of the clean UNGUARDED run.
  * RESUME — the fit killed at H/2 (after a durable checkpoint),
    resumed with ``resume_from=``; gate: final alpha within 1e-5 of the
    uninterrupted run.
"""
from __future__ import annotations

import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import KernelRidge, SolverOptions
from repro.core import KernelConfig
from repro.core.perf_model import guard_overhead
from repro.data.synthetic import regression_dataset
from repro.resilience import FaultPlan, SimulatedKill, inject

from .common import emit, save_json

OVERHEAD_GATE = 0.10
RECOVERY_TOL = 1e-5


def _fit_wall(mk, A, y, iters=3, **fit_kw):
    """Min-of-N wall-clock of a full fit (jit-warm after the first
    call; min is the noise-robust statistic for same-work timing)."""
    mk().fit(A, y, **fit_kw)                    # warm every jit cache
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        r = mk().fit(A, y, **fit_kw)
        jax.block_until_ready(r.alpha)
        ts.append(time.perf_counter() - t0)
    return min(ts), r


def resilience(fast: bool = False):
    m, n = (768, 32) if fast else (2048, 64)
    H = 4096 if fast else 8192
    s, b = 8, 8
    kern = KernelConfig("linear")
    A, y = regression_dataset(jax.random.key(0), m, n)
    base = dict(method="sstep", s=s, b=b, max_iters=H, seed=3)

    # ---- gate 1: guarded overhead at the autotuned cadence -----------
    plain_opts = SolverOptions(**base)
    guard_opts = SolverOptions(**base, guard=True)   # recompute="auto"
    t_plain, _ = _fit_wall(
        lambda: KernelRidge(lam=0.5, kernel=kern, options=plain_opts),
        A, y)
    t_guard, r_guard = _fit_wall(
        lambda: KernelRidge(lam=0.5, kernel=kern, options=guard_opts),
        A, y)
    rec = r_guard.options.recompute_every
    rounds = r_guard.rounds_run
    assert rounds > rec, \
        (f"sizing bug: {rounds} rounds at cadence {rec} — drift "
         f"correction never fired, the overhead measurement is vacuous")
    assert r_guard.health.corrections > 0
    overhead = t_guard / t_plain - 1.0
    modeled = guard_overhead(m, n, kern.name, b=b, s=s,
                             recompute_every=rec)
    emit("fig8/overhead", t_guard * 1e6,
         f"plain={t_plain * 1e6:.1f}us;recompute_every={rec};"
         f"measured={overhead:.3f};modeled={modeled:.3f}")

    # ---- gate 2: NaN recovery matches the clean unguarded run --------
    clean = KernelRidge(lam=0.5, kernel=kern, options=plain_opts)
    r_clean = clean.fit(A, y)
    with inject(FaultPlan(nan_at_iter=H // 3)) as fault:
        r_rec = KernelRidge(lam=0.5, kernel=kern,
                            options=guard_opts).fit(A, y)
    assert fault.carry_fired
    rec_err = float(jnp.max(jnp.abs(r_rec.alpha - r_clean.alpha)))
    emit("fig8/recovery", rec_err,
         f"fallbacks={[e.action for e in r_rec.health.fallbacks]}")

    # ---- gate 3: kill at H/2, resume from the durable checkpoint -----
    with tempfile.TemporaryDirectory() as ckpt:
        ck_opts = SolverOptions(**base, guard=True, checkpoint_every=64,
                                checkpoint_dir=ckpt)
        kr = KernelRidge(lam=0.5, kernel=kern, options=ck_opts)
        try:
            with inject(FaultPlan(kill_at_iter=H // 2)):
                kr.fit(A, y)
            raise AssertionError("simulated kill never fired")
        except SimulatedKill:
            pass
        r_res = kr.fit(A, y, resume_from=ckpt)
    full = KernelRidge(lam=0.5, kernel=kern,
                       options=SolverOptions(**base, guard=True)).fit(A, y)
    res_err = float(jnp.max(jnp.abs(r_res.alpha - full.alpha)))
    emit("fig8/resume", res_err,
         f"checkpoints={r_res.health.checkpoints};"
         f"resumed={r_res.health.resumed_from is not None}")

    save_json("fig8_resilience.json", {
        "m": m, "n": n, "H": H, "s": s, "b": b,
        "recompute_every": rec, "rounds": int(rounds),
        "corrections": int(r_guard.health.corrections),
        "max_drift": r_guard.health.max_drift,
        "t_plain_s": t_plain, "t_guarded_s": t_guard,
        "overhead_measured": overhead, "overhead_modeled": modeled,
        "recovery_max_abs_err": rec_err,
        "recovery_fallbacks": [e.action for e in r_rec.health.fallbacks],
        "resume_max_abs_err": res_err,
        "gates": {"overhead": OVERHEAD_GATE, "tol": RECOVERY_TOL}})

    assert overhead <= OVERHEAD_GATE, \
        (f"guarded overhead {overhead:.1%} exceeds the "
         f"{OVERHEAD_GATE:.0%} gate (modeled {modeled:.1%} at "
         f"recompute_every={rec})")
    assert rec_err <= RECOVERY_TOL, \
        f"NaN recovery error {rec_err} above {RECOVERY_TOL}"
    assert res_err <= RECOVERY_TOL, \
        f"resume-after-kill error {res_err} above {RECOVERY_TOL}"


def run(fast: bool = False):
    resilience(fast=fast)


if __name__ == "__main__":
    ap = __import__("argparse").ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    run(fast=ap.parse_args().fast)
