"""The exact operator's loop-ready form (``ExactGramOperator.for_rounds``).

The round-function factories build it once per solve, before the round
loop: A zero-padded to whole KMV blocks.  Checked here:

* structure: in every solve program, no pad of an (m, n) array runs
  per round, and exactly one runs before the loop (none where m is a
  whole number of blocks);
* parity: the loop-ready operator gives what the plain one gives, and a
  solve gives the alpha of the per-call pad it replaces;
* reach: the benchmark's KMV faults, which replace
  ``kernels.kmv_slab_free``, still reach the loop-ready operator.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.core import (KRRConfig, SVMConfig, bdcd_krr, block_schedule,
                        coordinate_schedule, dcd_ksvm, sstep_bdcd_krr,
                        sstep_dcd_ksvm)
from repro.core.kernels import (ExactGramOperator, KernelConfig,
                                apply_epilogue)

ROOT = Path(__file__).resolve().parents[1]

N = 6
RBF = KernelConfig("rbf", sigma=0.5)
KERNELS = [
    KernelConfig("linear"),
    KernelConfig("polynomial", degree=3, coef0=1.0),
    KernelConfig("rbf", sigma=0.7),
]


def _problem(m, H=32, b=2):
    ka, ky, ks = jax.random.split(jax.random.key(m), 3)
    A = jax.random.normal(ka, (m, N), jnp.float32) / np.sqrt(N)
    y = jnp.where(jax.random.bernoulli(ky, 0.5, (m,)), 1.0, -1.0)
    return (A, y, coordinate_schedule(ks, H, m),
            block_schedule(ks, H, m, b))


def _solve_jaxpr(case, m):
    """The jaxpr of one jitted solve program of ``case`` at m rows."""
    from repro.api import _guarded_serial_chunk, _krr_serial_tol
    from repro.tune.fleet import _fleet_serial
    A, y, csched, bsched = _problem(m)
    a0 = jnp.zeros(m)
    svm = SVMConfig(C=1.0, loss="l1", kernel=RBF)
    krr = KRRConfig(lam=1.0, kernel=RBF)
    op = ExactGramOperator(A, RBF)
    if case == "dcd":
        return jax.make_jaxpr(lambda A, y: dcd_ksvm(
            A, y, a0, csched, svm))(A, y)
    if case == "bdcd":
        return jax.make_jaxpr(lambda A, y: bdcd_krr(
            A, y, a0, bsched, krr))(A, y)
    if case == "sstep_dcd":
        return jax.make_jaxpr(lambda A, y: sstep_dcd_ksvm(
            A, y, a0, csched, svm, 4))(A, y)
    if case == "sstep_bdcd":
        return jax.make_jaxpr(lambda A, y: sstep_bdcd_krr(
            A, y, a0, bsched, krr, 4))(A, y)
    if case == "guarded":
        return jax.make_jaxpr(lambda op: _guarded_serial_chunk(
            A, y, a0, a0, bsched, jnp.asarray(1e-6), -1, float("nan"),
            problem="krr", cfg=krr, s=4, check_every=2, correct_every=2,
            lowrank=False, want_metric=True, op=op))(op)
    if case == "tol":
        return jax.make_jaxpr(lambda op: _krr_serial_tol(
            A, y, a0, bsched, jnp.asarray(1e-6), cfg=krr, s=4,
            check_every=2, slab_free=True, op=op))(op)
    assert case == "fleet"
    return jax.make_jaxpr(lambda op: _fleet_serial(
        A, y, jnp.zeros((2, m)), jnp.asarray([0.5, 1.0]), bsched,
        jnp.asarray(-jnp.inf), op, problem="krr", cfg=krr, s=4,
        check_every=1, want_metric=False))(op)


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(j, ClosedJaxpr):
                yield j.jaxpr
            elif isinstance(j, Jaxpr):
                yield j


def _a_pads(jaxpr, shape, in_loop=False, in_cond=False, out=None):
    """Pads of a ``shape`` operand: (per round, in a cond branch inside
    a loop, outside every loop).  A cond's branches run every
    ``check_every`` / ``correct_every`` rounds, not every round: the
    tolerance check's and the drift correction's full KMV over the
    plain A live there."""
    out = out if out is not None else {"round": 0, "cond": 0, "once": 0}
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pad" and tuple(eqn.invars[0].aval.shape) == shape:
            out["cond" if in_loop and in_cond
                else "round" if in_loop else "once"] += 1
        for sub in _subjaxprs(eqn):
            _a_pads(sub, shape, in_loop or name in ("scan", "while"),
                    in_cond or name == "cond", out)
    return out


CASES = ["dcd", "bdcd", "sstep_dcd", "sstep_bdcd", "guarded", "tol",
         "fleet"]


@pytest.mark.parametrize("m", [5000, 4096])
@pytest.mark.parametrize("case", CASES)
def test_a_is_padded_once_per_solve(case, m):
    pads = _a_pads(_solve_jaxpr(case, m).jaxpr, (m, N))
    assert pads["round"] == 0, pads
    # 5,000 rows pad to 3 blocks of 2,048 once; 4,096 are whole blocks
    assert pads["once"] == (1 if m % 2048 else 0), pads


# --- parity: the loop-ready operator against the plain one ---------------

@pytest.mark.parametrize("m", [300, 2047, 2049, 6144])
@pytest.mark.parametrize("cfg", KERNELS, ids=lambda k: k.name)
def test_loop_ready_operator_matches_the_plain_one(cfg, m):
    op = ExactGramOperator(_problem(m)[0], cfg)
    lr = op.for_rounds()
    idx = jnp.asarray([0, 5, m - 1, m // 2, 5])
    X = jax.random.normal(jax.random.key(1), (m, 3))
    w = jnp.asarray([1.0, -2.0, 0.5, 3.0, -1.0])
    Xq = op.rows(idx[:4]) + 0.1          # four queries near training rows
    assert lr.n_samples == op.n_samples == m
    assert lr.feature_dim == N and lr.dtype == op.dtype
    pad = 0 if cfg.name == "linear" else (-m) % min(2048, m)
    assert lr.round_pad_rows == op.round_pad_rows == pad
    assert lr.A.shape == (m + pad, N)    # the linear KMV has no blocks
    assert (lr is op) == (pad == 0) and lr.for_rounds() is lr
    for got, want in [(lr.rows(idx), op.rows(idx)),
                      (lr.diag(idx), op.diag(idx)),
                      (lr.cross_block(idx), op.cross_block(idx)),
                      (lr.matvec(idx, X[:, 0]), op.matvec(idx, X[:, 0])),
                      (lr.matvec(idx, X), op.matvec(idx, X)),
                      (lr.apply_at(idx, w), op.apply_at(idx, w)),
                      (lr.serve_block(Xq, X[:, 0]), op.serve_block(Xq, X[:, 0])),
                      (lr.take(idx[:3]).rows(jnp.arange(3)),
                       op.take(idx[:3]).rows(jnp.arange(3))),
                      (lr.full_matvec(X[:, 0]), op.full_matvec(X[:, 0]))]:
        assert got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _kmv_per_call_pad(A, B, X, cfg, block=2048):
    """The blocked KMV as it ran before the loop-ready form: A and X
    zero-padded to whole blocks on every call."""
    vec = X.ndim == 1
    Xc = X[:, None] if vec else X
    m, n = A.shape
    r, c = B.shape[0], Xc.shape[1]
    blk = min(block, m)
    pad = (-m) % blk
    Ap = jnp.pad(A, ((0, pad), (0, 0)))
    Xp = jnp.pad(Xc, ((0, pad), (0, 0)))
    cs = jnp.sum(B * B, axis=1) if cfg.name == "rbf" else None

    def body(acc, chunk):
        a_blk, x_blk = chunk
        dots = a_blk @ B.T
        if cfg.name == "rbf":
            Kb = apply_epilogue(dots, cfg, jnp.sum(a_blk * a_blk, axis=1), cs)
        else:
            Kb = apply_epilogue(dots, cfg)
        return acc + Kb.T @ x_blk, None

    out, _ = jax.lax.scan(body, jnp.zeros((r, c), Xc.dtype),
                          (Ap.reshape(-1, blk, n), Xp.reshape(-1, blk, c)))
    return out[:, 0] if vec else out


@pytest.mark.parametrize("m", [2049, 5000])
@pytest.mark.parametrize("cfg", KERNELS[1:], ids=lambda k: k.name)
@pytest.mark.parametrize("problem", ["ksvm", "krr"])
def test_solve_matches_the_per_call_pad(problem, cfg, m):
    """Eight s-step rounds (s = 4) through the loop-ready operator give
    the alpha of the per-call pad (an operator with a backend never
    takes the loop-ready form: it pads per call)."""
    A, y, csched, bsched = _problem(m)
    a0 = jnp.zeros(m)
    if problem == "ksvm":
        svm = SVMConfig(C=1.0, loss="l1", kernel=cfg)
        op = ExactGramOperator(A, cfg).scale_rows(y)
        ref = dataclasses.replace(op, matvec_impl=_kmv_per_call_pad)
        got = sstep_dcd_ksvm(A, y, a0, csched, svm, 4, op=op)[0]
        want = sstep_dcd_ksvm(A, y, a0, csched, svm, 4, op=ref)[0]
    else:
        krr = KRRConfig(lam=1.0, kernel=cfg)
        op = ExactGramOperator(A, cfg)
        ref = dataclasses.replace(op, matvec_impl=_kmv_per_call_pad)
        got = sstep_bdcd_krr(A, y, a0, bsched[:16], krr, 4, op=op)[0]
        want = sstep_bdcd_krr(A, y, a0, bsched[:16], krr, 4, op=ref)[0]
    assert np.any(np.asarray(got) != 0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- reach: the benchmark's KMV faults on the loop-ready operator ---------

@pytest.mark.parametrize("fault", ["half_rows", "zero_kmv"])
def test_kmv_faults_reach_the_loop_ready_operator(fault):
    sys.path.insert(0, str(ROOT))
    from bench import faults
    lr = ExactGramOperator(_problem(5000)[0], RBF).for_rounds()
    idx = jnp.arange(8)
    X = jnp.ones((5000,))
    jax.clear_caches()
    clean = np.asarray(jax.jit(lr.matvec)(idx, X))
    undo = faults.plant(fault)
    try:
        jax.clear_caches()
        faulty = np.asarray(jax.jit(lr.matvec)(idx, X))
    finally:
        undo()
        jax.clear_caches()
    assert not np.allclose(faulty, clean, rtol=1e-3), (faulty, clean)


@pytest.mark.parametrize("m", [2049, 4096])
def test_solve_span_carries_pad_rows(m):
    """The facade's ``solve`` span says how many zero rows the solve
    appended to A: 2,047 at 2,049 rows, 0 at whole blocks."""
    from repro.api import KernelSVM, SolverOptions
    from repro.obs import Telemetry
    A, y, _, _ = _problem(m)
    tel = Telemetry()
    KernelSVM(C=1.0, kernel="rbf", options=SolverOptions(
        method="sstep", s=4, max_iters=16, telemetry=tel)).fit(A, y)
    (solve,) = [sp for sp in tel.spans if sp.name == "solve"]
    assert solve.args["pad_rows"] == (-m) % 2048
