"""Guarded-solve tests (DESIGN.md §12): drift correction, divergence
detection + escalation-ladder fallback, mid-solve checkpoint/resume, and
the fault-injection harness.

NOTE: this module deliberately injects NaN/Inf into solver carries — it
must NOT be added to conftest.KERNEL_TEST_MODULES (jax_debug_nans would
raise at the injection site instead of letting the guard catch it).
"""
import os
import pathlib
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (KernelRidge, KernelSVM, KernelConfig,
                       SolverOptions)
from repro.resilience import (DivergenceError, FaultPlan, SimulatedKill,
                              finite_health, inject, next_fallback)

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def _data(m=192, n=12, seed=0):
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    w = rng.standard_normal(n)
    yc = jnp.asarray(np.sign(A @ w + 0.1 * rng.standard_normal(m)),
                     jnp.float32)
    yr = jnp.asarray(A @ w + 0.1 * rng.standard_normal(m), jnp.float32)
    return A, yc, yr


def _opts(**kw):
    base = dict(method="sstep", s=8, max_iters=384, seed=3,
                slab_free=True)
    base.update(kw)
    return SolverOptions(**base)


# ----------------------------------------------------------------- guard


@pytest.mark.parametrize("problem", ["ksvm", "krr"])
@pytest.mark.parametrize("kernel", ["linear", "rbf"])
def test_guarded_matches_plain(problem, kernel):
    """The guarded carry protocol is an algebraic rearrangement: same
    iterate sequence as the plain solver, to f32 roundoff."""
    A, yc, yr = _data()
    kcfg = (KernelConfig(kernel) if kernel == "linear"
            else KernelConfig("rbf", sigma=0.3))
    if problem == "ksvm":
        plain = KernelSVM(C=1.0, kernel=kcfg, options=_opts())
        guard = KernelSVM(C=1.0, kernel=kcfg,
                          options=_opts(guard=True, recompute_every=16))
        y = yc
    else:
        plain = KernelRidge(lam=0.5, kernel=kcfg, options=_opts(b=8))
        guard = KernelRidge(lam=0.5, kernel=kcfg,
                            options=_opts(b=8, guard=True,
                                          recompute_every=16))
        y = yr
    rp, rg = plain.fit(A, y), guard.fit(A, y)
    np.testing.assert_allclose(np.asarray(rp.alpha),
                               np.asarray(rg.alpha), atol=5e-6)
    assert rg.health is not None and rg.health.guarded
    assert rg.health.corrections > 0
    assert rg.health.max_drift < 1e-4
    assert rg.health.fallbacks == ()
    assert rp.health is None


def test_drift_history_recorded():
    A, yc, _ = _data()
    svm = KernelSVM(C=1.0, kernel="rbf",
                    options=_opts(guard=True, recompute_every=8))
    r = svm.fit(A, yc)
    h = r.health
    assert len(h.drift) == h.corrections
    assert np.all(np.isfinite(h.drift))
    assert h.recompute_every == 8


def test_recompute_every_auto_resolves_under_budget():
    from repro.core.perf_model import (GUARD_OVERHEAD_BUDGET,
                                       guard_overhead)
    A, yc, _ = _data()
    svm = KernelSVM(C=1.0, kernel="linear", options=_opts(guard=True))
    r = svm.fit(A, yc)
    rec = r.options.recompute_every
    assert isinstance(rec, int) and rec >= 1
    over = guard_overhead(A.shape[0], A.shape[1], "linear", s=8,
                          recompute_every=rec)
    assert over <= GUARD_OVERHEAD_BUDGET + 1e-12


# --------------------------------------------- divergence + the ladder


@pytest.mark.parametrize("target", ["f", "alpha"])
def test_nan_fault_recovers_to_clean_solution(target):
    """Acceptance: injected NaN -> guard discards the poisoned round,
    the ladder halves s, and the final alpha matches an unguarded clean
    run within 1e-5."""
    A, yc, _ = _data()
    clean = KernelSVM(C=1.0, kernel="rbf", options=_opts()).fit(A, yc)
    svm = KernelSVM(C=1.0, kernel="rbf",
                    options=_opts(guard=True, recompute_every=16))
    with inject(FaultPlan(nan_at_iter=96, target=target)) as plan:
        r = svm.fit(A, yc)
    assert plan.carry_fired
    fb = r.health.fallbacks
    assert len(fb) == 1 and fb[0].kind == "nonfinite"
    assert fb[0].action == "halve_s:8->4"
    np.testing.assert_allclose(np.asarray(r.alpha),
                               np.asarray(clean.alpha), atol=1e-5)


def test_ladder_descends_to_classical_then_f64():
    """Three injected faults walk halve_s -> halve_s -> halve_s; a fault
    on an already-classical run escalates to f64."""
    A, _, yr = _data()
    clean = KernelRidge(lam=0.5, kernel="linear",
                        options=_opts(b=4, method="classical")).fit(A, yr)
    kr = KernelRidge(lam=0.5, kernel="linear",
                     options=_opts(b=4, method="classical", guard=True))
    with inject(FaultPlan(nan_at_iter=40, target="alpha")):
        r = kr.fit(A, yr)
    assert [e.action for e in r.health.fallbacks] == ["f64"]
    np.testing.assert_allclose(np.asarray(r.alpha),
                               np.asarray(clean.alpha), atol=1e-5)


def test_f64_rung_refused_where_it_cannot_compile(monkeypatch):
    """On a backend without f64 linear solves (a TPU) the ladder stops at
    the f64 rung with an error naming the device, carrying its events."""
    import repro.api as api
    monkeypatch.setattr(api, "F64_UNSUPPORTED_PLATFORMS", ("cpu",))
    A, _, yr = _data()
    kr = KernelRidge(lam=0.5, kernel="linear",
                     options=_opts(b=4, method="classical", guard=True))
    with inject(FaultPlan(nan_at_iter=40, target="alpha")):
        with pytest.raises(DivergenceError,
                           match="f64 rung, which cpu device") as ei:
            kr.fit(A, yr)
    assert [e.action for e in ei.value.events] == ["f64"]


def test_fallback_disabled_raises():
    A, yc, _ = _data()
    svm = KernelSVM(C=1.0, kernel="rbf",
                    options=_opts(guard=True, fallback=False))
    with inject(FaultPlan(nan_at_iter=96)):
        with pytest.raises(DivergenceError, match="fallback is disabled"):
            svm.fit(A, yc)


def test_next_fallback_ladder():
    assert next_fallback(8, "sstep", False) == ("halve_s:8->4", 4,
                                                "sstep", False)
    assert next_fallback(2, "sstep", False)[1:] == (1, "sstep", False)
    assert next_fallback(1, "sstep", False) == ("classical", 1,
                                                "classical", False)
    assert next_fallback(1, "classical", False) == ("f64", 1,
                                                    "classical", True)
    with pytest.raises(DivergenceError, match="exhausted"):
        next_fallback(1, "classical", True)


def test_finite_health_sees_every_leaf():
    carry = (jnp.ones(4), jnp.zeros(3))
    assert bool(finite_health(carry))
    assert not bool(finite_health((carry[0].at[1].set(jnp.inf),
                                   carry[1])))
    assert not bool(finite_health((carry[0],
                                   carry[1].at[0].set(jnp.nan))))


# ------------------------------------------------- checkpoint / resume


def test_kill_and_resume_reaches_same_solution(tmp_path):
    """Acceptance: a fit killed mid-solve and resumed via resume_from=
    reaches the same solution as the uninterrupted run."""
    A, yc, _ = _data()
    d = str(tmp_path)
    opts = _opts(guard=True, recompute_every=16, checkpoint_every=8,
                 checkpoint_dir=d)
    full = KernelSVM(C=1.0, kernel="rbf",
                     options=_opts(guard=True, recompute_every=16))
    ref = full.fit(A, yc)

    svm = KernelSVM(C=1.0, kernel="rbf", options=opts)
    with inject(FaultPlan(kill_at_iter=192)) as plan:
        with pytest.raises(SimulatedKill) as ei:
            svm.fit(A, yc)
    assert plan.kill_fired
    assert ei.value.checkpoint_dir == d

    r = svm.fit(A, yc, resume_from=d)
    assert r.health.resumed_from == d
    assert r.health.events[0].kind == "resume"
    np.testing.assert_allclose(np.asarray(r.alpha),
                               np.asarray(ref.alpha), atol=1e-5)


def test_resume_refuses_foreign_checkpoint(tmp_path):
    A, yc, _ = _data()
    d = str(tmp_path)
    opts = _opts(guard=True, checkpoint_every=8, checkpoint_dir=d,
                 recompute_every=16)
    svm = KernelSVM(C=1.0, kernel="rbf", options=opts)
    with inject(FaultPlan(kill_at_iter=192)):
        with pytest.raises(SimulatedKill):
            svm.fit(A, yc)
    other = KernelSVM(C=1.0, kernel="rbf",
                      options=_opts(guard=True, recompute_every=16,
                                    seed=9))
    with pytest.raises(ValueError, match="fingerprint"):
        other.fit(A, yc, resume_from=d)


def test_resume_requires_guard():
    A, yc, _ = _data()
    svm = KernelSVM(C=1.0, kernel="rbf", options=_opts())
    with pytest.raises(ValueError, match="guard"):
        svm.fit(A, yc, resume_from="/nonexistent")


# ------------------------------------------------------ eager validation


def test_nonfinite_inputs_rejected_by_name():
    A, yc, _ = _data()
    svm = KernelSVM(C=1.0, kernel="rbf", options=_opts())
    with pytest.raises(ValueError, match=r"^A contains"):
        svm.fit(A.at[3, 2].set(jnp.nan), yc)
    with pytest.raises(ValueError, match=r"^y contains"):
        svm.fit(A, yc.at[0].set(jnp.inf))
    svm.fit(A, yc)
    with pytest.raises(ValueError, match=r"^A_test contains"):
        svm.predict(A.at[1, 1].set(jnp.nan))


def test_bad_hyperparameters_rejected_by_name():
    with pytest.raises(ValueError, match="C must be > 0"):
        KernelSVM(C=0.0)
    with pytest.raises(ValueError, match="lam must be > 0"):
        KernelRidge(lam=-1.0)


def test_guard_option_validation():
    with pytest.raises(ValueError, match="checkpoint_dir"):
        SolverOptions(guard=True, checkpoint_every=4)
    with pytest.raises(ValueError, match="guard"):
        SolverOptions(checkpoint_every=4, checkpoint_dir="/tmp/x")
    with pytest.raises(ValueError, match="recompute_every"):
        SolverOptions(guard=True, recompute_every=-1)
    with pytest.raises(ValueError, match="recompute_every"):
        SolverOptions(guard=True, recompute_every="sometimes")


# ------------------------------------------------------ distributed (1d)

_DIST_SCRIPT = r"""
import numpy as np, jax.numpy as jnp
from repro.api import KernelRidge, SolverOptions
from repro.resilience import FaultPlan, inject

rng = np.random.default_rng(0)
A = jnp.asarray(rng.standard_normal((128, 16)), jnp.float32)
y = jnp.asarray(A @ rng.standard_normal(16) + 0.1, jnp.float32)
kw = dict(method="sstep", s=8, b=8, max_iters=256, seed=3, layout="1d",
          slab_free=True)
plain = KernelRidge(lam=0.5, kernel="linear",
                    options=SolverOptions(**kw)).fit(A, y)
guard = KernelRidge(lam=0.5, kernel="linear",
                    options=SolverOptions(**kw, guard=True))
r = guard.fit(A, y)
assert np.allclose(np.asarray(plain.alpha), np.asarray(r.alpha)), \
    "guarded 1d != plain 1d"
with inject(FaultPlan(nan_at_iter=64)) as plan:
    rf = KernelRidge(lam=0.5, kernel="linear",
                     options=SolverOptions(**kw, guard=True)).fit(A, y)
assert plan.carry_fired
acts = [e.action for e in rf.health.fallbacks]
assert acts == ["halve_s:8->4"], acts
err = float(np.max(np.abs(np.asarray(rf.alpha) - np.asarray(plain.alpha))))
assert err < 1e-5, err
print("DIST-GUARD-OK")
"""


def test_guarded_1d_fault_recovery_subprocess():
    """Poisoned-psum fault on a 4-device host mesh: the chunk-boundary
    guard detects it, the ladder halves s, the re-run chunk recovers.
    Subprocess because device count must be set before jax init."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    out = subprocess.run([sys.executable, "-c", _DIST_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "DIST-GUARD-OK" in out.stdout
