"""Slab-free (GramOperator) solvers vs materialized-slab iterates.

Acceptance contract: the slab-free path must reproduce the
materialized-slab iterates to <= 1e-5 (f32) across all kernel x loss
combinations — the two paths differ ONLY in reduction order (blocked
contraction vs one slab GEMM), never in math.

Covers all four solvers: classical DCD/BDCD and the s-step variants with
s in {1, 4, 16}, for the three paper kernels x {L1, L2} SVM x KRR, plus
an interpret-mode Pallas-KMV run per solver family.  The materialized
side is ``repro.kernels.ref.SlabGramOperator``, passed as ``op_factory``.
The shard_map (distributed 1D/2D) parity lives in tests/dist_worker.py,
which checks each layout against the serial solvers under an 8-device
mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (KernelConfig, KRRConfig, SVMConfig, bdcd_krr,
                        block_schedule, coordinate_schedule, dcd_ksvm,
                        make_bdcd_round_fn, make_dcd_round_fn,
                        make_sstep_bdcd_round_fn, make_sstep_dcd_round_fn,
                        sstep_bdcd_krr, sstep_dcd_ksvm)
from repro.data.synthetic import classification_dataset, regression_dataset
from repro.kernels.ops import make_solver_op_factory
from repro.kernels.ref import SlabGramOperator

KERNELS = [
    KernelConfig("linear"),
    KernelConfig("polynomial", degree=3, coef0=1.0),
    KernelConfig("rbf", sigma=1.0),
]

TOL = dict(rtol=1e-5, atol=1e-5)        # acceptance bound (f32)


def _svm_problem(loss, kernel, m=96, n=24, H=16):
    A, y = classification_dataset(jax.random.key(0), m=m, n=n)
    cfg = SVMConfig(C=1.0, loss=loss, kernel=kernel)
    sched = coordinate_schedule(jax.random.key(1), H, m)
    return A, y, jnp.zeros(m), sched, cfg


def _krr_problem(kernel, m=80, n=12, H=16, b=4):
    A, y = regression_dataset(jax.random.key(2), m=m, n=n)
    cfg = KRRConfig(lam=0.5, kernel=kernel)
    sched = block_schedule(jax.random.key(3), H, m, b)
    return A, y, jnp.zeros(m), sched, cfg


@pytest.mark.parametrize("kernel", KERNELS, ids=lambda k: k.name)
@pytest.mark.parametrize("loss", ["l1", "l2"])
def test_dcd_slabfree_matches_materialized(kernel, loss):
    A, y, a0, sched, cfg = _svm_problem(loss, kernel)
    ref, _ = dcd_ksvm(A, y, a0, sched, cfg, op_factory=SlabGramOperator)
    got, _ = dcd_ksvm(A, y, a0, sched, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


@pytest.mark.parametrize("kernel", KERNELS, ids=lambda k: k.name)
@pytest.mark.parametrize("loss", ["l1", "l2"])
@pytest.mark.parametrize("s", [1, 4, 16])
def test_sstep_dcd_slabfree_matches_materialized(kernel, loss, s):
    A, y, a0, sched, cfg = _svm_problem(loss, kernel)
    ref, _ = sstep_dcd_ksvm(A, y, a0, sched, cfg, s=s,
                            op_factory=SlabGramOperator)
    got, _ = sstep_dcd_ksvm(A, y, a0, sched, cfg, s=s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


@pytest.mark.parametrize("kernel", KERNELS, ids=lambda k: k.name)
def test_bdcd_slabfree_matches_materialized(kernel):
    A, y, a0, sched, cfg = _krr_problem(kernel)
    ref, _ = bdcd_krr(A, y, a0, sched, cfg, op_factory=SlabGramOperator)
    got, _ = bdcd_krr(A, y, a0, sched, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


@pytest.mark.parametrize("kernel", KERNELS, ids=lambda k: k.name)
@pytest.mark.parametrize("s", [1, 4, 16])
def test_sstep_bdcd_slabfree_matches_materialized(kernel, s):
    A, y, a0, sched, cfg = _krr_problem(kernel)
    ref, _ = sstep_bdcd_krr(A, y, a0, sched, cfg, s=s,
                            op_factory=SlabGramOperator)
    got, _ = sstep_bdcd_krr(A, y, a0, sched, cfg, s=s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


@pytest.mark.parametrize("kernel", KERNELS, ids=lambda k: k.name)
def test_sstep_dcd_pallas_kmv_backend(kernel):
    """Interpret-mode Pallas KMV behind the operator, vs materialized."""
    A, y, a0, sched, cfg = _svm_problem("l2", kernel, m=48, n=32, H=16)
    factory = make_solver_op_factory(use_pallas=True, interpret=True,
                                     bm=16, br=8, bk=128)
    ref, _ = sstep_dcd_ksvm(A, y, a0, sched, cfg, s=4,
                            op_factory=SlabGramOperator)
    got, _ = sstep_dcd_ksvm(A, y, a0, sched, cfg, s=4, op_factory=factory)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


@pytest.mark.parametrize("kernel", KERNELS, ids=lambda k: k.name)
def test_sstep_bdcd_pallas_kmv_backend(kernel):
    A, y, a0, sched, cfg = _krr_problem(kernel, m=64, n=16, H=8, b=4)
    factory = make_solver_op_factory(use_pallas=True, interpret=True,
                                     bm=16, br=8, bk=128)
    ref, _ = sstep_bdcd_krr(A, y, a0, sched, cfg, s=4,
                            op_factory=SlabGramOperator)
    got, _ = sstep_bdcd_krr(A, y, a0, sched, cfg, s=4, op_factory=factory)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


def test_slabfree_still_matches_classical_equivalence():
    """End-to-end: slab-free s-step DCD still equals classical DCD (the
    paper's Section 3 claim must survive the operator rewiring)."""
    A, y, a0, sched, cfg = _svm_problem("l1", KernelConfig("rbf"), H=32)
    a_dcd, _ = dcd_ksvm(A, y, a0, sched, cfg)
    a_ss, _ = sstep_dcd_ksvm(A, y, a0, sched, cfg, s=8)
    np.testing.assert_allclose(np.asarray(a_ss), np.asarray(a_dcd),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("factory,problem,s", [
    (make_dcd_round_fn, "svm", ()), (make_bdcd_round_fn, "krr", ()),
    (make_sstep_dcd_round_fn, "svm", (4,)),
    (make_sstep_bdcd_round_fn, "krr", (4,)),
], ids=["dcd", "bdcd", "sstep_dcd", "sstep_bdcd"])
def test_round_fn_takes_one_operator_source(factory, problem, s):
    """A round fn reads the kernel through ONE operator: a factory or a
    prebuilt operator, never both."""
    kernel = KernelConfig("rbf")
    A, y, _, _, cfg = (_svm_problem("l1", kernel) if problem == "svm"
                       else _krr_problem(kernel))
    with pytest.raises(ValueError, match="at most one"):
        factory(A, y, cfg, *s, op_factory=SlabGramOperator,
                op=SlabGramOperator(A, kernel))
