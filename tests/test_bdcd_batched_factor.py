"""s-step BDCD factors a round's s diagonal b x b blocks once, batched.

``sstep_bdcd_inner`` used to build ``G_j`` and call ``jnp.linalg.solve``
inside each of the s steps of its loop.  Every ``G_j`` is a diagonal block
of the round's cross block, known before the first step, so the round
now factors all s at once and each step only applies block j's inverse.

* parity: the inner recurrence, the guarded-carry round and the fleet's
  vmap over ``lam`` give what the per-step solve gives (kept here as the
  reference), to 1e-5 relative in float32, with coordinates that collide
  across blocks and a ragged final round whose padded blocks give 0;
* structure: the compiled round holds one factorization, outside the
  inner loop's ``while``; the per-step formulation holds it inside.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hlo_loops import factorizations, loop_computations
from repro.core import KernelConfig, KRRConfig
from repro.core import sstep_bdcd
from repro.core.kernels import ExactGramOperator, gram_slab
from repro.core.loop import pad_rounds

RBF = KernelConfig("rbf", sigma=1.0)


def _per_step_inner(Gblk, QTalpha, alpha_at, y_at, flat, m, inv_lam, s, b,
                    valid=None):
    """The per-step formulation: build and solve ``G_j`` in step j."""
    dtype = alpha_at.dtype
    ones = jnp.ones((s,), dtype) if valid is None else valid.astype(dtype)
    collide4 = (flat[:, None] == flat[None, :]).astype(dtype).reshape(
        s, b, s, b)
    Gblk4 = Gblk.reshape(s, b, s, b)
    eye_b = jnp.eye(b, dtype=dtype)

    def inner(j, dalpha):
        prior = dalpha * (jnp.arange(s) < j).astype(dtype)[:, None]
        vv = jnp.einsum("tq,tqp->p", prior, collide4[:, :, j, :])
        uv = jnp.einsum("tq,tqp->p", prior, Gblk4[:, :, j, :])
        Uj_idx = jax.lax.dynamic_slice_in_dim(
            Gblk4[:, :, j, :].reshape(s * b, b), j * b, b, axis=0)
        G = inv_lam * Uj_idx + m * eye_b
        rhs = (y_at[j] - m * alpha_at[j] - m * vv
               - inv_lam * jax.lax.dynamic_slice_in_dim(QTalpha, j * b, b)
               - inv_lam * uv)
        return dalpha.at[j].set(jnp.linalg.solve(G, rhs) * ones[j])

    return jax.lax.fori_loop(0, s, inner, jnp.zeros((s, b), dtype))


def _problem(m=512, n=16, seed=0):
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.standard_normal((m, n)) / np.sqrt(n), jnp.float32)
    y = jnp.asarray(np.sin(np.asarray(A) @ rng.standard_normal(n)),
                    jnp.float32)
    alpha = jnp.asarray(rng.standard_normal(m) * 1e-3, jnp.float32)
    return A, y, alpha


def _blocks(m, s, b, rounds, seed=0):
    """(rounds * s - pad, b) blocks, distinct inside a block, drawn from
    a small pool so that blocks share coordinates; block 1 repeats block
    0's first coordinate; the last round is ragged when s > 1."""
    rng = np.random.default_rng(seed)
    pool = min(m, 3 * b)
    H = rounds * s - s // 3
    sched = np.stack([rng.choice(pool, b, replace=False) for _ in range(H)])
    if H > 1:
        sched[1, 0] = sched[0, 0]
    return jnp.asarray(sched, jnp.int32)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("lam", [0.1, 1e-6])
@pytest.mark.parametrize("b", [1, 8])
@pytest.mark.parametrize("s", [1, 4, 16])
def test_inner_matches_per_step_solves(s, b, lam):
    """One ragged round: the last s // 3 blocks padded (index 0, valid 0)
    as ``pad_rounds`` pads them, so they give exactly 0."""
    A, y, alpha = _problem()
    m = A.shape[0]
    xs_idx, xs_valid = pad_rounds(_blocks(m, s, b, 1), s)
    idx, valid = xs_idx[0], xs_valid[0]
    flat = idx.reshape(s * b)
    assert s == 1 or len(set(np.asarray(flat).tolist())) < s * b
    Gblk = gram_slab(A[flat], A[flat], RBF)
    QTalpha = gram_slab(A, A[flat], RBF).T @ alpha
    args = (Gblk, QTalpha, alpha[idx], y[idx], flat, m, 1.0 / lam, s, b,
            valid)
    got = jax.jit(sstep_bdcd.sstep_bdcd_inner, static_argnums=(7, 8))(*args)
    want = jax.jit(_per_step_inner, static_argnums=(7, 8))(*args)
    pad = np.asarray(valid) == 0
    assert pad.sum() == s // 3
    assert np.all(np.asarray(got)[pad] == 0.0)
    assert np.all(np.isfinite(np.asarray(got)))
    assert _rel(got, want) <= 1e-5


def _rounds(round_fn, state, xs):
    return jax.lax.scan(lambda c, x: (round_fn(c, x), None), state, xs)[0]


@pytest.mark.parametrize("lam", [0.1, 1e-6])
@pytest.mark.parametrize("s", [4, 16])
def test_guarded_round_matches_per_step_solves(monkeypatch, s, lam):
    """Three ragged rounds of the guarded-carry round fn (``f = K alpha``
    carried): alpha and f as with the per-step solves."""
    A, y, alpha = _problem()
    b = 8
    op = ExactGramOperator(A, RBF)
    xs = pad_rounds(_blocks(A.shape[0], s, b, 3, seed=1), s)
    state0 = (alpha, op.full_matvec(alpha))

    def run():
        rf = sstep_bdcd.make_sstep_bdcd_round_fn(
            A, y, KRRConfig(lam=lam, kernel=RBF), s, op=op, guard=True)
        return jax.jit(lambda st, x: _rounds(rf, st, x))(state0, xs)

    got = run()
    monkeypatch.setattr(sstep_bdcd, "sstep_bdcd_inner", _per_step_inner)
    want = run()
    for g, w, g0 in zip(got, want, state0):
        assert _rel(g - g0, w - g0) <= 1e-5


@pytest.mark.parametrize("s", [4, 16])
def test_fleet_vmap_over_lam_matches_per_step_solves(monkeypatch, s):
    """The fleet's lockstep round: one operator, the round vmapped over
    ``lam`` (0.1 and 1e-6 together), so the batched factorization gains
    the fleet's axis."""
    A, y, alpha = _problem()
    b = 8
    op = ExactGramOperator(A, RBF).for_rounds()
    lams = jnp.asarray([0.1, 1e-6], jnp.float32)
    xs = pad_rounds(_blocks(A.shape[0], s, b, 3, seed=2), s)
    alphas = jnp.stack([alpha, alpha])
    cfg = KRRConfig(kernel=RBF)

    def run():
        def member(a, lam, x):
            rf = sstep_bdcd.make_sstep_bdcd_round_fn(A, y, cfg, s, op=op,
                                                     lam=lam)
            return rf(a, x)
        vround = jax.vmap(member, in_axes=(0, 0, None))
        return jax.jit(lambda st, x: _rounds(
            lambda c, xx: vround(c, lams, xx), st, x))(alphas, xs)

    got = run()
    monkeypatch.setattr(sstep_bdcd, "sstep_bdcd_inner", _per_step_inner)
    want = run()
    for f in range(len(lams)):
        assert _rel(got[f] - alpha, want[f] - alpha) <= 1e-5


def _round_hlo(s, b):
    A, y, alpha = _problem(m=64, n=6)
    sched = _blocks(A.shape[0], s, b, 4)
    return sstep_bdcd.sstep_bdcd_krr.lower(
        A, y, alpha, sched, KRRConfig(kernel=RBF), s).compile().as_text()


@pytest.mark.parametrize("s,b", [(4, 4), (16, 8)])
def test_round_factors_once_outside_the_inner_loop(s, b):
    """The compiled round loop holds one factorization (a batched
    ``potrf`` on the CPU), and the inner loop's ``while`` none."""
    hlo = _round_hlo(s, b)
    in_rounds = factorizations(hlo, 1)
    assert loop_computations(hlo, 2), "no inner loop compiled"
    assert len(in_rounds) == 1 and "potrf" in in_rounds[0], in_rounds
    assert not factorizations(hlo, 2), factorizations(hlo, 2)
    assert factorizations(hlo) == in_rounds


def test_per_step_formulation_factors_inside_the_inner_loop(monkeypatch):
    """The structure check sees the difference: with the per-step solves
    the factorization sits in the inner loop's body."""
    monkeypatch.setattr(sstep_bdcd, "sstep_bdcd_inner", _per_step_inner)
    hlo = _round_hlo(4, 2)           # a shape of its own: a fresh trace
    in_steps = factorizations(hlo, 2)
    assert in_steps and all("getrf" in f for f in in_steps)
