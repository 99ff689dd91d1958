"""The ``repro.tune`` subsystem contract (ISSUE 5, DESIGN.md §10):

  * a vmapped fleet of F lambdas/Cs is elementwise-equal (scan path,
    shared schedule) to F sequential facade fits — serial AND 1d;
  * fleet tolerance stopping is per member: converged members freeze,
    the history is (checks, F), and every converged member really is at
    or below tol under the facade's own metric;
  * warm-started solves at tight tolerance land on the cold solution
    (property test over seeds/lambdas);
  * ``reg_path`` spends no more total iterations than cold solves and
    its rungs match cold fits at the same tolerance;
  * ``cross_validate`` reports per-fold, per-value scores for both
    composition modes (fleet, path);
  * ``SolverOptions(s="auto")`` resolves through the perf model for
    BOTH representations (exact, nystrom), respects the HBM working-set
    constraint (as does ``perf_model.best_s``), and lands its
    ``TunedPlan`` on ``FitResult.plan``;
  * Nystrom kmeans landmark draws are reproducible end-to-end from
    ``SolverOptions.seed``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import KernelRidge, KernelSVM, SolverOptions
from repro.core import KernelConfig, KRRConfig, NO_TOL, run_rounds
from repro.core.perf_model import Machine, Problem, best_s, slab_fits_hbm
from repro.data.synthetic import classification_dataset, regression_dataset
from repro.tune import (TunedPlan, cross_validate, reg_path,
                        resolve_options, solve_fleet)

M, N, H, S, B = 96, 16, 64, 8, 4
LAMS = (0.25, 1.0, 4.0, 16.0)
CS = (0.25, 1.0, 4.0)
TOL = dict(rtol=1e-5, atol=1e-5)


@pytest.fixture(scope="module")
def krr_data():
    return regression_dataset(jax.random.key(0), m=M, n=N)


@pytest.fixture(scope="module")
def svm_data():
    return classification_dataset(jax.random.key(1), m=M, n=N)


def _opts(**kw):
    base = dict(method="sstep", s=S, b=B, max_iters=H, seed=5)
    base.update(kw)
    return SolverOptions(**base)


# ---------------------------------------------------------------------------
# fleet parity vs sequential facade fits
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["serial", "1d"])
def test_krr_fleet_matches_sequential(krr_data, layout):
    A, y = krr_data
    opts = _opts(layout=layout)
    fleet = solve_fleet(A, y, lams=LAMS, kernel="rbf", options=opts)
    assert fleet.alpha.shape == (len(LAMS), M)
    for i, lam in enumerate(LAMS):
        ref = KernelRidge(lam=lam, kernel="rbf", options=opts).fit(A, y)
        np.testing.assert_allclose(np.asarray(fleet.alpha[i]),
                                   np.asarray(ref.alpha), **TOL)


@pytest.mark.parametrize("layout", ["serial", "1d"])
def test_ksvm_fleet_matches_sequential(svm_data, layout):
    A, y = svm_data
    opts = _opts(b=1, layout=layout)
    fleet = solve_fleet(A, y, Cs=CS, kernel="rbf", options=opts)
    for i, C in enumerate(CS):
        ref = KernelSVM(C=C, kernel="rbf", options=opts).fit(A, y)
        np.testing.assert_allclose(np.asarray(fleet.alpha[i]),
                                   np.asarray(ref.alpha), **TOL)


def test_nystrom_fleet_matches_sequential(krr_data):
    A, y = krr_data
    opts = _opts(approx="nystrom", landmarks=24)
    fleet = solve_fleet(A, y, lams=LAMS, kernel="rbf", options=opts)
    assert fleet.representation == "nystrom(l=24)"
    for i, lam in enumerate(LAMS):
        ref = KernelRidge(lam=lam, kernel="rbf", options=opts).fit(A, y)
        np.testing.assert_allclose(np.asarray(fleet.alpha[i]),
                                   np.asarray(ref.alpha), **TOL)


def test_fleet_modeled_comm_amortizes(krr_data):
    A, y = krr_data
    fleet = solve_fleet(A, y, lams=LAMS, kernel="rbf", options=_opts())
    assert fleet.comm["modeled_speedup"] > 1.0
    assert fleet.comm["sequential_time"] > fleet.comm["time"]


def test_fleet_input_validation(krr_data):
    A, y = krr_data
    with pytest.raises(ValueError, match="exactly one"):
        solve_fleet(A, y, lams=LAMS, Cs=CS)
    with pytest.raises(ValueError, match="exactly one"):
        solve_fleet(A, y)
    with pytest.raises(ValueError, match="positive"):
        solve_fleet(A, y, lams=[1.0, -2.0])
    with pytest.raises(ValueError, match="fleet layout"):
        solve_fleet(A, y, lams=LAMS, options=_opts(layout="2d"))


# ---------------------------------------------------------------------------
# per-member tolerance stopping
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["serial", "1d"])
def test_fleet_per_member_stopping(krr_data, layout):
    A, y = krr_data
    opts = _opts(layout=layout, max_iters=1024, tol=5e-2, check_every=2)
    fleet = solve_fleet(A, y, lams=LAMS, kernel="rbf", options=opts)
    assert fleet.converged.all()
    assert fleet.history.shape[1] == len(LAMS)
    assert fleet.metric == "rel_residual"
    # every member's final state satisfies the facade's own stopper
    from repro.core import krr_rel_residual
    for i, lam in enumerate(LAMS):
        cfg = KRRConfig(lam=float(lam), kernel=KernelConfig("rbf"))
        assert float(krr_rel_residual(A, y, fleet.alpha[i], cfg)) <= 5e-2
    # member trajectories are per-member, not fleet-wide copies
    assert fleet.metric_history(0).shape == fleet.metric_history(1).shape
    assert not np.allclose(fleet.metric_history(0),
                           fleet.metric_history(len(LAMS) - 1))


def test_fleet_frozen_members_do_not_drift(krr_data):
    """A member that converges early must hold its state while the rest
    of the fleet keeps iterating (the vmap-safe freeze mask)."""
    A, y = krr_data
    # lam -> inf converges almost immediately; lam small converges last
    lams = (1000.0, 0.25)
    opts = _opts(max_iters=2048, tol=2e-2, check_every=2)
    fleet = solve_fleet(A, y, lams=lams, kernel="rbf", options=opts)
    assert fleet.converged.all()
    hist = fleet.metric_history(0)
    k = int(np.argmax(hist <= 2e-2))
    # once member 0 hit tol, its recorded metric never changes again
    np.testing.assert_allclose(hist[k:], hist[k], rtol=1e-6)


# ---------------------------------------------------------------------------
# warm starts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("lam", [0.5, 4.0])
def test_warm_start_matches_cold_property(seed, lam):
    """Property: a warm-started solve at tight tol lands on the same
    solution as a cold solve — the warm start changes the trajectory,
    not the fixed point."""
    A, y = regression_dataset(jax.random.key(100 + seed), m=64, n=8)
    opts = _opts(max_iters=4096, tol=1e-5, check_every=4, seed=seed)
    reg = KernelRidge(lam=lam, kernel="rbf", options=opts)
    cold = reg.fit(A, y)
    assert cold.converged
    # warm-start from a perturbed neighbourhood of another solution
    other = KernelRidge(lam=4.0 * lam, kernel="rbf", options=opts)
    w0 = other.fit(A, y).alpha
    warm = reg.fit(A, y, warm_start=w0)
    assert warm.converged
    assert warm.iters_run <= cold.iters_run
    np.testing.assert_allclose(np.asarray(warm.alpha),
                               np.asarray(cold.alpha), rtol=5e-4,
                               atol=5e-5)


def test_reg_path_warm_start_saves_iterations(krr_data):
    A, y = krr_data
    opts = _opts(max_iters=4096, tol=2e-2, check_every=4)
    path = reg_path(A, y, lams=LAMS, kernel="rbf", options=opts)
    assert path.param == "lam"
    assert list(path.values) == sorted(LAMS, reverse=True)
    assert all(r.converged for r in path.results)
    cold_total = sum(
        KernelRidge(lam=float(v), kernel="rbf", options=opts)
        .fit(A, y).iters_run for v in path.values)
    assert path.total_iters < cold_total
    # each rung matches its cold twin at the same tolerance scale
    for v, r in zip(path.values, path.results):
        cold = KernelRidge(lam=float(v), kernel="rbf",
                           options=opts).fit(A, y)
        np.testing.assert_allclose(np.asarray(r.alpha),
                                   np.asarray(cold.alpha), rtol=0.05,
                                   atol=5e-3)


def test_fit_path_updates_estimator_state(krr_data):
    A, y = krr_data
    opts = _opts(max_iters=1024, tol=5e-2, check_every=4)
    reg = KernelRidge(lam=123.0, kernel="rbf", options=opts)
    path = reg.fit_path(A, y, LAMS)
    assert reg.cfg.lam == float(path.values[-1]) == min(LAMS)
    np.testing.assert_allclose(np.asarray(reg.alpha_),
                               np.asarray(path.results[-1].alpha))
    assert reg.predict(A).shape == (M,)


def test_ksvm_fit_path(svm_data):
    A, y = svm_data
    opts = _opts(b=1, max_iters=512)
    clf = KernelSVM(C=1.0, kernel="rbf", options=opts)
    path = clf.fit_path(A, y, CS)
    assert path.param == "C"
    assert list(path.values) == sorted(CS)        # ascending C ladder
    assert clf.cfg.C == max(CS)
    assert clf.predict(A).shape == (M,)


# ---------------------------------------------------------------------------
# cross-validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("via", ["fleet", "path"])
def test_cross_validate_krr(krr_data, via):
    A, y = krr_data
    opts = _opts(max_iters=512, tol=5e-2, check_every=4)
    cv = cross_validate(A, y, lams=LAMS, kernel="rbf", options=opts,
                        folds=3, via=via)
    assert cv.scores.shape == (3, len(LAMS))
    assert cv.score_name == "mse" and np.all(cv.scores > 0)
    assert cv.best_value == cv.values[cv.best_index]
    assert cv.mean_scores[cv.best_index] == cv.mean_scores.min()


def test_cross_validate_ksvm():
    # wide-margin blobs: genuinely separable, so accuracy is informative
    A, y = classification_dataset(jax.random.key(9), m=M, n=N,
                                  margin=3.0)
    cv = cross_validate(A, y, Cs=CS, kernel="rbf",
                        options=_opts(b=1, max_iters=256), folds=3)
    assert cv.score_name == "accuracy"
    assert np.all((cv.scores >= 0) & (cv.scores <= 1))
    assert cv.mean_scores[cv.best_index] > 0.8


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("approx", [None, "nystrom"])
def test_auto_s_resolves_through_perf_model(krr_data, approx):
    A, y = krr_data
    opts = _opts(s="auto", approx=approx, landmarks=24)
    assert opts.needs_autotune
    res = KernelRidge(lam=1.0, kernel="rbf", options=opts).fit(A, y)
    plan = res.plan
    assert isinstance(plan, TunedPlan)
    assert isinstance(res.options.s, int) and res.options.s >= 1
    assert res.options.approx == approx
    assert len(plan.frontier) > 1
    # the winner is the cheapest FEASIBLE modeled candidate
    feas = [f for f in plan.frontier if f["feasible"]]
    assert plan.modeled["time"] == min(f["time"] for f in feas)
    # and the solve actually ran with it
    assert res.alpha.shape == (M,)


def test_auto_b_and_unresolved_s_eff(krr_data):
    A, y = krr_data
    opts = _opts(s="auto", b="auto")
    with pytest.raises(ValueError, match="unresolved"):
        _ = opts.s_eff
    res = KernelRidge(lam=1.0, kernel="rbf", options=opts).fit(A, y)
    assert isinstance(res.options.s, int)
    assert isinstance(res.options.b, int)


def test_auto_ksvm_with_probe(svm_data):
    A, y = svm_data
    opts = _opts(b=1, s="auto", probe=2, max_iters=32)
    res = KernelSVM(C=1.0, kernel="rbf", options=opts).fit(A, y)
    assert res.plan.probed is not None and len(res.plan.probed) >= 1
    assert all("measured_s" in p for p in res.plan.probed)


def test_autotune_respects_hbm_constraint():
    """With a tiny HBM budget the tuner must refuse deep s even when the
    model says deeper is faster."""
    cfg = KRRConfig(lam=1.0, kernel=KernelConfig("rbf"))
    opts = SolverOptions(method="sstep", s="auto", b=8, max_iters=1024)
    budget = 4 * 50_000 * 8 * 4        # only slabs with s*b < 32 fit
    plan = resolve_options(50_000, 64, cfg, opts, problem="krr",
                           hbm_bytes=budget)
    s = plan.options.s
    assert s == 1 or slab_fits_hbm(50_000, s * 8, budget)
    infeasible = [f for f in plan.frontier if not f["feasible"]]
    assert infeasible, "frontier must expose the clipped candidates"


def test_autotune_pinned_infeasible_s_does_not_crash():
    """A PINNED s above the HBM budget must not crash the tuner (the
    feasibility filter only guards what autotune itself selects): the
    remaining auto knobs resolve best-effort toward the smallest
    working set."""
    cfg = KRRConfig(lam=1.0, kernel=KernelConfig("rbf"))
    opts = SolverOptions(method="sstep", s=256, b="auto", max_iters=1024)
    budget = 4 * 50_000 * 8            # nothing with s=256 fits
    plan = resolve_options(50_000, 64, cfg, opts, problem="krr",
                           hbm_bytes=budget)
    assert plan.options.s == 256       # the pinned knob is respected
    assert plan.options.b == 1         # smallest working set wins
    assert not any(f["feasible"] for f in plan.frontier)


def test_best_s_respects_feasibility():
    prob = Problem(m=1 << 20, n=64, b=8, H=1024)
    mach = Machine()
    budget = 64 * 2 ** 20              # 64 MiB: only tiny slabs fit
    s, t, frontier = best_s(prob, mach, P=64, hbm_bytes=budget,
                            return_frontier=True)
    assert s == 1 or slab_fits_hbm(prob.m, s * prob.b, budget)
    assert any(not f["feasible"] for f in frontier)
    # unconstrained search may pick deeper s (the constraint binds)
    s_free, _ = best_s(prob, mach, P=64)
    assert s_free >= s


def test_solver_options_auto_validation():
    with pytest.raises(ValueError, match="positive int"):
        SolverOptions(s="AUTO")
    with pytest.raises(ValueError, match="positive int"):
        SolverOptions(b=0)
    with pytest.raises(ValueError, match="probe"):
        SolverOptions(probe=-1)
    assert SolverOptions(s="auto", b="auto", layout="auto",
                         approx="auto").needs_autotune
    assert not SolverOptions().needs_autotune


# ---------------------------------------------------------------------------
# satellites: metric_history accessor, reproducible Nystrom seeding
# ---------------------------------------------------------------------------

def test_metric_history_accessors(krr_data):
    A, y = krr_data
    res = KernelRidge(lam=1.0, kernel="rbf",
                      options=_opts(record=True, check_every=2)).fit(A, y)
    np.testing.assert_array_equal(res.metric_history(), res.history)
    assert len(res.metric_history()) == -(-res.rounds_run // 2)
    # no-record fits expose None, not a stale buffer
    res2 = KernelRidge(lam=1.0, kernel="rbf", options=_opts()).fit(A, y)
    assert res2.metric_history() is None
    # LoopResult-level accessor slices the padded buffer to checks_run
    lr = run_rounds(lambda a, x: a + 1.0, jnp.zeros(()),
                    jnp.zeros((7,)), tol=NO_TOL, check_every=3,
                    metric_fn=lambda a: a)
    assert lr.metric_history().shape == (int(lr.checks_run),)


@pytest.mark.parametrize("method", ["uniform", "kmeans"])
def test_nystrom_seed_reproducible_end_to_end(krr_data, method):
    """SolverOptions.seed alone must pin the landmark draw — kmeans
    farthest-first included — so Nystrom fits replay exactly."""
    A, y = krr_data
    mk = lambda seed: KernelRidge(
        lam=1.0, kernel="rbf",
        options=_opts(approx="nystrom", landmarks=16,
                      landmark_method=method, seed=seed))
    m1, m2, m3 = (mk(s) for s in (7, 7, 8))
    a1, a2, a3 = m1.fit(A, y), m2.fit(A, y), m3.fit(A, y)
    np.testing.assert_array_equal(np.asarray(m1.op_.fmap.landmarks),
                                  np.asarray(m2.op_.fmap.landmarks))
    np.testing.assert_array_equal(np.asarray(a1.alpha),
                                  np.asarray(a2.alpha))
    assert not np.array_equal(np.asarray(m1.op_.fmap.landmarks),
                              np.asarray(m3.op_.fmap.landmarks))
