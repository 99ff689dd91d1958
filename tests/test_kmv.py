"""Fused KMV Pallas kernel (interpret mode on CPU) vs the materialized
oracle, plus the slab-free jnp contraction and GramOperator surface.

The contract under test: ``kmv(A, B, X) == K(A, B)^T X`` for all three
paper kernels, any (non-block-aligned) shape, vector and multi-column X —
WITHOUT the kernel ever writing the m x r slab (structural property of
the Pallas grid; numerics checked here)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kernels import (ExactGramOperator, KernelConfig, gram_slab,
                                kernel_diag, kmv_slab_free)
from repro.kernels.kmv import kmv_pallas
from repro.kernels.ref import kmv_ref

KERNELS = [
    KernelConfig("linear"),
    KernelConfig("polynomial", degree=3, coef0=1.0),
    KernelConfig("rbf", sigma=0.7),
]


def _data(m, r, n, c, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(jax.random.key(m * 100 + r * 10 + n), 3)
    A = jax.random.normal(k1, (m, n), jnp.float32).astype(dtype)
    B = jax.random.normal(k2, (r, n), jnp.float32).astype(dtype)
    X = jax.random.normal(k3, (m, c), jnp.float32)
    return A, B, X


def assert_close_to_terms(got, want, terms, *, rtol, atol):
    """``|got - want| <= atol + rtol * terms`` elementwise: allclose with
    the magnitude of the summed TERMS in place of ``|want|``.  Rounding
    error of a sum grows with the sum of its terms' magnitudes, not with
    the (possibly cancelled) sum itself — degree-3 polynomial outputs
    at n=384 sum terms of ~1e5 that cancel down to ~1e2."""
    err = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    bound = atol + rtol * np.asarray(terms, np.float64)
    assert np.all(err <= bound), (
        f"max excess {np.max(err - bound):.3e}; worst err/terms "
        f"{np.max(err / np.maximum(terms, 1e-30)):.3e} > rtol {rtol}")


def _check_pallas(m, r, n, c, cfg, dtype=jnp.float32, bm=32, br=16, bk=128):
    A, B, X = _data(m, r, n, c, dtype)
    got = kmv_pallas(A, B, X, cfg, bm=bm, br=br, bk=bk, interpret=True)
    want = kmv_ref(A, B, X, cfg)
    U = gram_slab(A.astype(jnp.float32), B.astype(jnp.float32), cfg)
    terms = np.abs(np.asarray(U)).T @ np.abs(np.asarray(X))
    # bf16 inputs are rounded before either side sees them, so both
    # compute in f32 from the same values: one bound for both dtypes.
    # 1e-5 ~ 170 f32 unit roundoffs: a length-384 dot's rounding, times
    # the epilogue's amplification (3x for the cube, sigma*|a-b|^2 for
    # exp), times the m-term sum — measured worst ~1e-6.
    assert_close_to_terms(got, want, terms, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("cfg", KERNELS, ids=lambda k: k.name)
@pytest.mark.parametrize("shape", [(96, 24, 64, 1), (64, 32, 256, 4),
                                   (33, 17, 100, 2), (8, 1, 16, 1),
                                   (130, 70, 384, 3)])
def test_kmv_matches_oracle_f32(cfg, shape):
    _check_pallas(*shape, cfg=cfg)


@pytest.mark.parametrize("cfg", KERNELS, ids=lambda k: k.name)
def test_kmv_matches_oracle_bf16_inputs(cfg):
    _check_pallas(64, 24, 256, 2, cfg=cfg, dtype=jnp.bfloat16)


@pytest.mark.parametrize("blocks", [(16, 8, 128), (32, 32, 256),
                                    (64, 16, 128)])
def test_kmv_block_shape_invariance(blocks):
    bm, br, bk = blocks
    _check_pallas(96, 40, 384, 2, cfg=KernelConfig("rbf", sigma=1.0),
                  bm=bm, br=br, bk=bk)


@pytest.mark.parametrize("cfg", KERNELS, ids=lambda k: k.name)
def test_kmv_vector_rhs(cfg):
    """(m,) X must round-trip as a vector, matching the (m, 1) result."""
    A, B, X = _data(48, 12, 64, 1)
    got = kmv_pallas(A, B, X[:, 0], cfg, bm=16, br=8, bk=128,
                     interpret=True)
    assert got.shape == (12,)
    want = kmv_ref(A, B, X, cfg)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("cfg", KERNELS, ids=lambda k: k.name)
@pytest.mark.parametrize("shape", [(96, 24, 64, 1), (50, 7, 33, 3)])
def test_kmv_slab_free_jnp_matches_oracle(cfg, shape):
    """The blocked-scan jnp contraction (GramOperator default backend)."""
    m, r, n, c = shape
    A, B, X = _data(m, r, n, c)
    got = kmv_slab_free(A, B, X, cfg, block=16)
    want = kmv_ref(A, B, X, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("cfg", KERNELS, ids=lambda k: k.name)
def test_gram_operator_surface(cfg):
    """matvec / cross_block / diag / round_data against slab algebra."""
    A, _, X = _data(60, 1, 40, 1)
    idx = jnp.array([3, 17, 3, 59, 0])          # duplicates allowed
    op = ExactGramOperator(A, cfg, block=16)
    U = gram_slab(A, A[idx], cfg)
    np.testing.assert_allclose(np.asarray(op.matvec(idx, X[:, 0])),
                               np.asarray(U.T @ X[:, 0]), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(op.cross_block(idx)),
                               np.asarray(U[idx, :]), rtol=1e-6, atol=1e-6)
    # diag is EXACT (1.0 for RBF) while the slab diagonal suffers
    # ||a-a||^2 cancellation — compare at the slab's accuracy.
    np.testing.assert_allclose(np.asarray(op.diag(idx)),
                               np.asarray(jnp.diagonal(U[idx, :])),
                               rtol=1e-5, atol=1e-5)
    G, uTx = op.round_data(idx, X[:, 0])
    np.testing.assert_allclose(np.asarray(G), np.asarray(U[idx, :]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(uTx), np.asarray(U.T @ X[:, 0]),
                               rtol=2e-5, atol=2e-5)


def test_kernel_diag_matches_gram_diagonal():
    A = jax.random.normal(jax.random.key(7), (20, 16))
    for cfg in KERNELS:
        want = jnp.diagonal(gram_slab(A, A, cfg))
        np.testing.assert_allclose(np.asarray(kernel_diag(A, cfg)),
                                   np.asarray(want), rtol=1e-5, atol=1e-6)


def test_kmv_pallas_operator_end_to_end():
    """s-step DCD driven by the Pallas-KMV GramOperator backend == the
    materialized-slab solver (kernels.ops.make_solver_op_factory path)."""
    from repro.core import SVMConfig, coordinate_schedule, sstep_dcd_ksvm
    from repro.core.kernels import gram_slab as gs
    from repro.data.synthetic import classification_dataset
    from repro.kernels.ops import make_solver_op_factory

    A, y = classification_dataset(jax.random.key(1), m=48, n=32)
    cfg = SVMConfig(C=1.0, loss="l2", kernel=KernelConfig("rbf"))
    sched = coordinate_schedule(jax.random.key(2), 16, 48)
    a0 = jnp.zeros(48)
    ref, _ = sstep_dcd_ksvm(A, y, a0, sched, cfg, s=8, gram_fn=gs)
    factory = make_solver_op_factory(use_pallas=True, interpret=True,
                                     bm=16, br=8, bk=128)
    got, _ = sstep_dcd_ksvm(A, y, a0, sched, cfg, s=8, op_factory=factory)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
