"""The paper's central mathematical claim: the s-step variants compute the
SAME iterates as the classical methods in exact arithmetic (Section 3).
We verify it in fp32 (tight tol) and fp64 (machine precision)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import enable_x64
from repro.core import (KernelConfig, KRRConfig, SVMConfig, bdcd_krr,
                        block_schedule, coordinate_schedule, dcd_ksvm,
                        krr_closed_form, ksvm_duality_gap,
                        relative_solution_error, sstep_bdcd_krr,
                        sstep_dcd_ksvm)
from repro.data.synthetic import classification_dataset, regression_dataset

KERNELS = [
    KernelConfig("linear"),
    KernelConfig("polynomial", degree=3, coef0=1.0),
    KernelConfig("rbf", sigma=1.0),
]


@pytest.mark.parametrize("kernel", KERNELS, ids=lambda k: k.name)
@pytest.mark.parametrize("loss", ["l1", "l2"])
@pytest.mark.parametrize("s", [2, 8, 32])
def test_sstep_dcd_matches_dcd(kernel, loss, s):
    key = jax.random.key(0)
    A, y = classification_dataset(key, m=96, n=24)
    cfg = SVMConfig(C=1.0, loss=loss, kernel=kernel)
    H = 64
    sched = coordinate_schedule(jax.random.key(1), H, A.shape[0])
    a0 = jnp.zeros(A.shape[0])
    a_dcd, _ = dcd_ksvm(A, y, a0, sched, cfg)
    a_ss, _ = sstep_dcd_ksvm(A, y, a0, sched, cfg, s=s)
    np.testing.assert_allclose(np.asarray(a_ss), np.asarray(a_dcd),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("kernel", KERNELS, ids=lambda k: k.name)
@pytest.mark.parametrize("b", [1, 2, 4])
@pytest.mark.parametrize("s", [4, 16])
def test_sstep_bdcd_matches_bdcd(kernel, b, s):
    key = jax.random.key(2)
    A, y = regression_dataset(key, m=80, n=12)
    cfg = KRRConfig(lam=0.5, kernel=kernel)
    H = 32
    sched = block_schedule(jax.random.key(3), H, A.shape[0], b)
    a0 = jnp.zeros(A.shape[0])
    a_bd, _ = bdcd_krr(A, y, a0, sched, cfg)
    a_ss, _ = sstep_bdcd_krr(A, y, a0, sched, cfg, s=s)
    np.testing.assert_allclose(np.asarray(a_ss), np.asarray(a_bd),
                               rtol=2e-4, atol=2e-5)


def test_equivalence_fp64_machine_precision():
    """Paper: 'compute the same solution as the existing methods in exact
    arithmetic' — at fp64 the deviation should be ~1e-12."""
    with enable_x64(True):
        key = jax.random.key(4)
        A, y = classification_dataset(key, m=64, n=16, dtype=jnp.float64)
        cfg = SVMConfig(C=1.0, loss="l1", kernel=KernelConfig("rbf"))
        sched = coordinate_schedule(jax.random.key(5), 64, 64)
        a0 = jnp.zeros(64, jnp.float64)
        a_dcd, _ = dcd_ksvm(A, y, a0, sched, cfg)
        a_ss, _ = sstep_dcd_ksvm(A, y, a0, sched, cfg, s=16)
        np.testing.assert_allclose(np.asarray(a_ss), np.asarray(a_dcd),
                                   rtol=1e-10, atol=1e-12)


def test_dcd_duality_gap_decreases():
    key = jax.random.key(6)
    A, y = classification_dataset(key, m=64, n=16)
    cfg = SVMConfig(C=1.0, loss="l1", kernel=KernelConfig("rbf"))
    sched = coordinate_schedule(jax.random.key(7), 512, 64)
    a0 = jnp.zeros(64)
    a_mid, _ = dcd_ksvm(A, y, a0, sched[:64], cfg)
    a_end, _ = dcd_ksvm(A, y, a0, sched, cfg)
    g0 = float(ksvm_duality_gap(A, y, a0, cfg))
    g1 = float(ksvm_duality_gap(A, y, a_mid, cfg))
    g2 = float(ksvm_duality_gap(A, y, a_end, cfg))
    assert g1 < g0 and g2 < g1
    assert g2 >= -1e-5   # gap stays nonnegative (weak duality)


def test_bdcd_converges_to_closed_form():
    key = jax.random.key(8)
    A, y = regression_dataset(key, m=48, n=8)
    cfg = KRRConfig(lam=1.0, kernel=KernelConfig("rbf"))
    astar = krr_closed_form(A, y, cfg)
    sched = block_schedule(jax.random.key(9), 600, 48, 8)
    a, _ = bdcd_krr(A, y, jnp.zeros(48), sched, cfg)
    assert float(relative_solution_error(a, astar)) < 1e-4


def test_sstep_bdcd_converges_to_closed_form_large_s():
    """Paper Fig. 2: numerically stable even for s=256."""
    key = jax.random.key(10)
    A, y = regression_dataset(key, m=48, n=8)
    cfg = KRRConfig(lam=1.0, kernel=KernelConfig("rbf"))
    astar = krr_closed_form(A, y, cfg)
    sched = block_schedule(jax.random.key(11), 512, 48, 4)
    a, _ = sstep_bdcd_krr(A, y, jnp.zeros(48), sched, cfg, s=256)
    assert float(relative_solution_error(a, astar)) < 1e-3


@pytest.mark.parametrize("s", [8, 32, 256])
@pytest.mark.parametrize("problem", ["ksvm", "krr"])
def test_guarded_sstep_stability_matrix(problem, s):
    """Numerical-stability matrix (DESIGN.md §12): the GUARDED s-step
    path — residual recurrence + periodic drift correction — matches the
    classical iterates in f32 even at deep s, and the recorded drift
    stays at roundoff level (no divergent residual-error growth in s)."""
    from repro.api import KernelRidge, KernelSVM, SolverOptions

    key = jax.random.key(20)
    H = 512
    opts = dict(max_iters=H, seed=5)
    # cadence 1: s=256 leaves only ceil(512/256)=2 outer rounds, so the
    # correction must fire every round to be exercised at every s
    guard = dict(guard=True, recompute_every=1)
    if problem == "ksvm":
        A, y = classification_dataset(key, m=96, n=24)
        mk = lambda **kw: KernelSVM(
            C=1.0, kernel=KernelConfig("rbf", sigma=1.0),
            options=SolverOptions(**opts, **kw))
    else:
        A, y = regression_dataset(key, m=96, n=12)
        mk = lambda **kw: KernelRidge(
            lam=0.5, kernel=KernelConfig("rbf", sigma=1.0),
            options=SolverOptions(b=4, **opts, **kw))
    classical = mk(method="classical").fit(A, y)
    deep = mk(method="sstep", s=s, **guard).fit(A, y)
    np.testing.assert_allclose(np.asarray(deep.alpha),
                               np.asarray(classical.alpha),
                               rtol=2e-4, atol=2e-5)
    assert deep.health.corrections > 0
    assert deep.health.max_drift < 1e-4
