"""The ``repro.api`` facade contract (ISSUE 3 acceptance criteria):

  * ``KernelSVM``/``KernelRidge`` + ``SolverOptions`` dispatch to every
    (method, layout) in {classical, sstep} x {serial, 1d, 2d} and match
    the legacy functional entrypoints' iterates to <= 1e-5 in f32;
  * tolerance-based early stopping terminates for every variant with a
    decreasing reported metric history;
  * bad ``SolverOptions`` raise eagerly (at construction);
  * ``H % s != 0`` no longer raises — the masked final short round keeps
    parity with the classical solvers (pad-and-mask, DESIGN.md §8).

The 1d/2d layouts run on an auto-built 1-device mesh here (the main
pytest process must keep seeing one device, per the dry-run contract);
the real 8-device parity sweep lives in tests/dist_worker.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import FitResult, KernelRidge, KernelSVM, SolverOptions
from repro.core import (KernelConfig, bdcd_krr, dcd_ksvm, sstep_bdcd_krr,
                        sstep_dcd_ksvm)
from repro.data.synthetic import classification_dataset, regression_dataset

KERNELS = [
    KernelConfig("linear"),
    KernelConfig("polynomial", degree=3, coef0=1.0),
    KernelConfig("rbf", sigma=1.0),
]
METHODS = ("classical", "sstep")
LAYOUTS = ("serial", "1d", "2d")
TOL = dict(rtol=1e-5, atol=1e-5)

M, N, H, S, B = 64, 16, 32, 8, 4


@pytest.fixture(scope="module")
def svm_data():
    return classification_dataset(jax.random.key(0), m=M, n=N)


@pytest.fixture(scope="module")
def krr_data():
    return regression_dataset(jax.random.key(2), m=M, n=8)


# ---------------------------------------------------------------------------
# dispatch parity vs the legacy functional entrypoints
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel", KERNELS, ids=lambda k: k.name)
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("method", METHODS)
def test_ksvm_matches_legacy(svm_data, kernel, method, layout):
    A, y = svm_data
    opts = SolverOptions(method=method, s=S, layout=layout, max_iters=H)
    clf = KernelSVM(C=1.0, loss="l1", kernel=kernel, options=opts)
    res = clf.fit(A, y)
    a0 = jnp.zeros(M)
    if method == "classical":
        ref, _ = dcd_ksvm(A, y, a0, res.schedule, clf.cfg)
    else:
        ref, _ = sstep_dcd_ksvm(A, y, a0, res.schedule, clf.cfg, s=S)
    np.testing.assert_allclose(np.asarray(res.alpha), np.asarray(ref),
                               **TOL)
    # predict runs through the fitted state
    assert clf.predict(A).shape == (M,)


@pytest.mark.parametrize("kernel", KERNELS, ids=lambda k: k.name)
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("method", METHODS)
def test_krr_matches_legacy(krr_data, kernel, method, layout):
    A, y = krr_data
    opts = SolverOptions(method=method, s=S, b=B, layout=layout,
                         max_iters=H)
    reg = KernelRidge(lam=0.5, kernel=kernel, options=opts)
    res = reg.fit(A, y)
    a0 = jnp.zeros(M)
    if method == "classical":
        ref, _ = bdcd_krr(A, y, a0, res.schedule, reg.cfg)
    else:
        ref, _ = sstep_bdcd_krr(A, y, a0, res.schedule, reg.cfg, s=S)
    np.testing.assert_allclose(np.asarray(res.alpha), np.asarray(ref),
                               **TOL)
    assert reg.predict(A).shape == (M,)


def test_slab_free_false_matches_materialized_oracle(svm_data):
    """The facade's fit == the entry point on the materialized-slab
    reference operator, on the fit's own schedule."""
    A, y = svm_data
    opts = SolverOptions(method="sstep", s=S, max_iters=H)
    res = KernelSVM(kernel="rbf", options=opts).fit(A, y)
    from repro.kernels.ref import SlabGramOperator
    ref, _ = sstep_dcd_ksvm(A, y, jnp.zeros(M), res.schedule,
                            KernelSVM(kernel="rbf").cfg, s=S,
                            op_factory=SlabGramOperator)
    np.testing.assert_allclose(np.asarray(res.alpha), np.asarray(ref),
                               **TOL)


# ---------------------------------------------------------------------------
# tolerance-based stopping
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("method", METHODS)
def test_krr_tol_stops_every_variant(krr_data, method, layout):
    A, y = krr_data
    opts = SolverOptions(method=method, s=S, b=B, layout=layout,
                         tol=5e-2, check_every=2, max_iters=800)
    res = KernelRidge(lam=1.0, kernel="rbf", options=opts).fit(A, y)
    assert res.converged
    assert res.iters_run < 800
    assert res.metric == "rel_residual"
    hist = res.metric_history()
    assert hist is not None and len(hist) >= 1
    # reported history decreases overall and ends at/below tol
    assert hist[-1] <= 5e-2
    assert hist[-1] <= hist[0]


@pytest.mark.parametrize("layout", LAYOUTS)
def test_ksvm_tol_stops(svm_data, layout):
    A, y = svm_data
    # pick a reachable gap threshold: the gap after a full H run
    opts0 = SolverOptions(method="sstep", s=S, max_iters=256, record=True)
    base = KernelSVM(C=1.0, kernel="rbf", options=opts0).fit(A, y)
    target = float(base.metric_history()[-1]) * 1.05
    opts = SolverOptions(method="sstep", s=S, layout=layout, tol=target,
                         check_every=2, max_iters=1024)
    res = KernelSVM(C=1.0, kernel="rbf", options=opts).fit(A, y)
    assert res.converged and res.iters_run < 1024
    assert res.metric == "duality_gap"
    assert res.metric_history()[-1] <= target


def test_record_without_tol_runs_full_budget(krr_data):
    A, y = krr_data
    opts = SolverOptions(method="sstep", s=S, b=B, tol=0.0, record=True,
                         check_every=2, max_iters=H)
    res = KernelRidge(lam=1.0, kernel="rbf", options=opts).fit(A, y)
    assert not res.converged
    assert res.iters_run == H
    n_rounds = -(-H // S)
    hist = res.metric_history()
    assert len(hist) == -(-n_rounds // 2)
    assert hist[-1] <= hist[0]


# ---------------------------------------------------------------------------
# eager SolverOptions validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bad", [
    dict(method="sgd"),
    dict(layout="3d"),
    dict(s=0),
    dict(s="16"),
    dict(b=0),
    dict(b=-4),
    dict(max_iters=0),
    dict(check_every=0),
    dict(tol=-1e-3),
    dict(tol=float("nan")),
], ids=lambda d: ",".join(f"{k}={v}" for k, v in d.items()))
def test_solver_options_validate_eagerly(bad):
    with pytest.raises(ValueError):
        SolverOptions(**bad)


def test_mesh_axis_names_validated(svm_data):
    A, y = svm_data
    mesh = jax.make_mesh((1,), ("rows",))
    opts = SolverOptions(layout="1d", mesh=mesh, max_iters=8)
    with pytest.raises(ValueError, match="mesh lacks axes"):
        KernelSVM(options=opts).fit(A, y)


# ---------------------------------------------------------------------------
# ragged tails: H % s != 0 no longer raises, parity holds
# ---------------------------------------------------------------------------

class TestRaggedTail:
    @pytest.mark.parametrize("kernel", KERNELS, ids=lambda k: k.name)
    @pytest.mark.parametrize("H_ragged", [5, 27, 50])
    def test_sstep_dcd_ragged_matches_dcd(self, svm_data, kernel,
                                          H_ragged):
        A, y = svm_data
        from repro.core import SVMConfig, coordinate_schedule
        cfg = SVMConfig(C=1.0, loss="l1", kernel=kernel)
        sched = coordinate_schedule(jax.random.key(1), H_ragged, M)
        a0 = jnp.zeros(M)
        assert H_ragged % 16 != 0
        ref, _ = dcd_ksvm(A, y, a0, sched, cfg)
        got, _ = sstep_dcd_ksvm(A, y, a0, sched, cfg, s=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("kernel", KERNELS, ids=lambda k: k.name)
    @pytest.mark.parametrize("H_ragged", [3, 13, 27])
    def test_sstep_bdcd_ragged_matches_bdcd(self, krr_data, kernel,
                                            H_ragged):
        A, y = krr_data
        from repro.core import KRRConfig, block_schedule
        cfg = KRRConfig(lam=0.5, kernel=kernel)
        sched = block_schedule(jax.random.key(3), H_ragged, M, B)
        a0 = jnp.zeros(M)
        assert H_ragged % 8 != 0
        ref, _ = bdcd_krr(A, y, a0, sched, cfg)
        got, _ = sstep_bdcd_krr(A, y, a0, sched, cfg, s=8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_facade_ragged_every_layout(self, svm_data, layout):
        """H=27, s=8 -> 4 rounds, last one short — all layouts agree
        with classical DCD."""
        A, y = svm_data
        opts = SolverOptions(method="sstep", s=8, layout=layout,
                             max_iters=27)
        clf = KernelSVM(C=1.0, kernel="rbf", options=opts)
        res = clf.fit(A, y)
        ref, _ = dcd_ksvm(A, y, jnp.zeros(M), res.schedule, clf.cfg)
        np.testing.assert_allclose(np.asarray(res.alpha),
                                   np.asarray(ref), rtol=2e-4, atol=2e-5)
        assert res.rounds_run == 4 and res.iters_run == 27


# ---------------------------------------------------------------------------
# FitResult bookkeeping
# ---------------------------------------------------------------------------

def test_fit_result_comm_model_scales_with_s(krr_data):
    """The modeled comm cost must reflect the paper's claim: s-step
    sends ~the same words in 1/s as many messages."""
    A, y = krr_data
    fits = {}
    for method, s in (("classical", 1), ("sstep", 8)):
        opts = SolverOptions(method=method, s=s, b=B, max_iters=H)
        fits[method] = KernelRidge(kernel="rbf", options=opts).fit(A, y)
    assert isinstance(fits["sstep"], FitResult)
    assert fits["sstep"].comm["msgs"] < fits["classical"].comm["msgs"]
    assert fits["sstep"].wall_time_s > 0.0
    for fr in fits.values():
        assert {"flops", "words", "msgs", "time"} <= set(fr.comm)


# ---------------------------------------------------------------------------
# LoopResult accessor edge cases (DESIGN.md §8: host-sync accessors)
# ---------------------------------------------------------------------------

class TestLoopResultAccessors:
    """metric_history()/drift_history() edge cases: empty schedules,
    fleet shapes, and the unguarded/no-cadence distinction."""

    def _round_fn(self):
        return lambda a, x: 0.5 * a

    def test_check_cadence_beyond_budget_final_check_fires(self):
        """A check cadence beyond the round budget still runs exactly
        one check — the driver forces a final-round check so converged
        is never stale — and metric_history() is the (1,) slice of the
        recorded buffer."""
        from repro.core.loop import run_rounds
        res = run_rounds(self._round_fn(), jnp.ones(4),
                         jnp.zeros((8,), jnp.int32), tol=1e-6,
                         check_every=100,
                         metric_fn=lambda a: jnp.linalg.norm(a))
        assert int(res.checks_run) == 1
        hist = res.metric_history()
        assert hist is not None and hist.shape == (1,)
        assert np.isfinite(np.asarray(hist)).all()

    def test_scan_mode_history_is_none(self):
        from repro.core.loop import run_rounds
        res = run_rounds(self._round_fn(), jnp.ones(4),
                         jnp.zeros((8,), jnp.int32))
        assert res.metric_history() is None
        assert res.drift_history() is None

    def test_fleet_history_shape(self):
        """run_rounds_fleet records (n_checks, F); metric_history()
        slices the leading check axis and keeps F."""
        from repro.core.loop import NO_TOL, run_rounds_fleet
        F, m = 3, 4
        state0 = jnp.ones((F, m))
        res = run_rounds_fleet(
            lambda a, x: 0.5 * a, state0, jnp.zeros((8,), jnp.int32),
            tol=NO_TOL, check_every=2,
            metric_fn=lambda a: jnp.linalg.norm(a, axis=1))
        hist = res.metric_history()
        assert hist.shape == (int(res.checks_run), F)
        assert int(res.checks_run) == 4
        assert res.converged.shape == (F,)

    def test_drift_history_unguarded_is_none(self):
        from repro.core.loop import run_rounds
        res = run_rounds(self._round_fn(), jnp.ones(4),
                         jnp.zeros((8,), jnp.int32), tol=1e-30,
                         metric_fn=lambda a: jnp.linalg.norm(a))
        assert res.drift_history() is None

    def test_drift_history_no_cadence_is_none(self):
        """guard= with correct_every=0 records no drift buffer at all:
        drift_history() is None (distinct from an empty slice)."""
        from repro.core.loop import GuardSpec, run_rounds
        guard = GuardSpec(
            health_fn=lambda a: jnp.all(jnp.isfinite(a)))
        res = run_rounds(self._round_fn(), jnp.ones(4),
                         jnp.zeros((8,), jnp.int32), tol=1e-30,
                         metric_fn=lambda a: jnp.linalg.norm(a),
                         guard=guard)
        assert res.drift_history() is None
        assert int(res.diverged_round) == -1

    def test_drift_history_cadence_never_fired_is_empty(self):
        """A guarded run whose cadence exceeds the round budget returns
        the empty (0,) slice — the buffer exists, nothing was
        recorded."""
        from repro.core.loop import GuardSpec, run_rounds
        guard = GuardSpec(
            health_fn=lambda a: jnp.all(jnp.isfinite(a)),
            correct_fn=lambda a: (a, jnp.asarray(0.0)),
            correct_every=100)
        res = run_rounds(self._round_fn(), jnp.ones(4),
                         jnp.zeros((8,), jnp.int32), tol=1e-30,
                         metric_fn=lambda a: jnp.linalg.norm(a),
                         guard=guard)
        drift = res.drift_history()
        assert drift is not None and drift.shape == (0,)
