"""Pallas fused gram kernel vs the pure-jnp oracle (interpret mode on CPU).

Sweeps shapes (including non-block-aligned), dtypes, and all three paper
kernels; plus a hypothesis property sweep."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:                                    # property sweep is optional on bare envs
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.core.kernels import KernelConfig
from repro.kernels.gram import gram_pallas
from repro.kernels.ref import gram_ref

KERNELS = [
    KernelConfig("linear"),
    KernelConfig("polynomial", degree=3, coef0=1.0),
    KernelConfig("rbf", sigma=0.7),
]


def _check(m, r, n, cfg, dtype, bm=32, br=32, bk=128):
    k1, k2 = jax.random.split(jax.random.key(m * 1000 + r * 10 + n))
    A = jax.random.normal(k1, (m, n), jnp.float32).astype(dtype)
    B = jax.random.normal(k2, (r, n), jnp.float32).astype(dtype)
    got = gram_pallas(A, B, cfg, bm=bm, br=br, bk=bk, interpret=True)
    want = gram_ref(A, B, cfg)
    # f32 tol covers reduction-order differences (blocked k accumulation);
    # bf16 inputs dominate with their own rounding.
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("cfg", KERNELS, ids=lambda c: c.name)
@pytest.mark.parametrize("shape", [(32, 32, 128), (64, 32, 256),
                                   (33, 17, 100), (8, 8, 128),
                                   (130, 70, 384)])
def test_gram_matches_oracle_f32(cfg, shape):
    _check(*shape, cfg=cfg, dtype=jnp.float32)


@pytest.mark.parametrize("cfg", KERNELS, ids=lambda c: c.name)
def test_gram_matches_oracle_bf16(cfg):
    _check(64, 48, 256, cfg=cfg, dtype=jnp.bfloat16)


@pytest.mark.parametrize("blocks", [(8, 8, 128), (16, 32, 256), (64, 64, 128)])
def test_gram_block_shape_invariance(blocks):
    bm, br, bk = blocks
    _check(96, 80, 384, cfg=KernelConfig("rbf", sigma=1.0),
           dtype=jnp.float32, bm=bm, br=br, bk=bk)


if HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None)
    @given(m=st.integers(1, 70), r=st.integers(1, 40), n=st.integers(1, 150),
           kidx=st.integers(0, 2))
    def test_gram_property_shapes(m, r, n, kidx):
        """Any (m, r, n) — padding must never contaminate real outputs."""
        _check(m, r, n, cfg=KERNELS[kidx], dtype=jnp.float32,
               bm=16, br=16, bk=128)


def test_gram_rbf_diagonal_is_one():
    A = jax.random.normal(jax.random.key(0), (40, 64))
    out = gram_pallas(A, A, KernelConfig("rbf", sigma=1.0),
                      bm=16, br=16, bk=128, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.diagonal(out)), 1.0, atol=1e-4)


def test_solver_with_pallas_gram_matches_jnp_gram():
    """End-to-end: s-step DCD on the reference slab operator built by the
    Pallas gram == on the default (jnp) operator."""
    from functools import partial

    from repro.core import (KernelConfig, SVMConfig, coordinate_schedule,
                            sstep_dcd_ksvm)
    from repro.kernels.ref import SlabGramOperator
    from repro.data.synthetic import classification_dataset

    A, y = classification_dataset(jax.random.key(1), m=48, n=32)
    cfg = SVMConfig(C=1.0, loss="l2", kernel=KernelConfig("rbf"))
    sched = coordinate_schedule(jax.random.key(2), 16, 48)
    a0 = jnp.zeros(48)
    ref, _ = sstep_dcd_ksvm(A, y, a0, sched, cfg, s=8)

    def pallas_gram(Am, Bm, kcfg):
        return gram_pallas(Am, Bm, kcfg, bm=16, br=16, bk=128,
                           interpret=True).astype(Am.dtype)

    got, _ = sstep_dcd_ksvm(A, y, a0, sched, cfg, s=8,
                            op_factory=partial(SlabGramOperator,
                                               gram=pallas_gram))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
