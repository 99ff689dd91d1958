"""K-RR's b x b solves under their own scope, and counted on the solve span.

* ``block_solve`` is one of the round's phase names (``repro.obs.SCOPES``);
* the compiled s-step and classical BDCD programs run their b x b
  factorizations and solves under it, inside ``recurrence``, so ``owner``
  gives it for the solve's ops and ``recurrence`` keeps the corrections;
  the DCD programs never reach it;
* the facade's ``solve`` span carries ``b`` (1 for K-SVM) beside ``s``,
  so a K-RR fit's block solves are the span's ``s`` times its rounds;
* s-step BDCD at the benchmark's s = 16, b = 8 still gives the plain
  reference's classical BDCD (``bench/reference.py``).
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import KernelRidge, KernelSVM, SolverOptions
from repro.core import KernelConfig, KRRConfig, SVMConfig
from repro.obs import SCOPES, Telemetry
from repro.obs.scopes import BLOCK_SOLVE, RECURRENCE, owner

ROOT = Path(__file__).resolve().parents[1]


def _problem(m=64, n=6, seed=0):
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.standard_normal((m, n)) / np.sqrt(n), jnp.float32)
    y = jnp.asarray(np.sin(np.asarray(A) @ rng.standard_normal(n)),
                    jnp.float32)
    return A, y


def _lowered(driver):
    from repro.core.bdcd import bdcd_krr
    from repro.core.dcd import dcd_ksvm
    from repro.core.sstep_bdcd import sstep_bdcd_krr
    from repro.core.sstep_dcd import sstep_dcd_ksvm
    A, y = _problem()
    a0 = jnp.zeros(A.shape[0])
    blocks = jnp.tile(jnp.arange(4, dtype=jnp.int32), (16, 1))
    if driver == "sstep_bdcd":             # s = 4, b = 4
        return sstep_bdcd_krr.lower(A, y, a0, blocks, KRRConfig(), 4)
    if driver == "bdcd":
        return bdcd_krr.lower(A, y, a0, blocks, KRRConfig())
    coords = jnp.zeros(16, jnp.int32)
    if driver == "sstep_dcd":
        return sstep_dcd_ksvm.lower(A, jnp.sign(y), a0, coords, SVMConfig(),
                                    4)
    return dcd_ksvm.lower(A, jnp.sign(y), a0, coords, SVMConfig())


def _op_names(driver):
    text = _lowered(driver).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


def test_block_solve_is_a_phase_name():
    assert BLOCK_SOLVE == "block_solve" and BLOCK_SOLVE in SCOPES


@pytest.mark.parametrize("driver", ["sstep_bdcd", "bdcd"])
def test_bdcd_programs_own_their_solves(driver):
    names = _op_names(driver)
    solve = [p for p in names if re.search(r"/(lu|triangular_solve)", p)]
    assert solve, "no LU or triangular solve in the compiled program"
    assert {owner(p) for p in solve} == {BLOCK_SOLVE}
    # the solve sits inside the recurrence, which keeps the corrections
    assert all(f"/{RECURRENCE}/" in p.split(BLOCK_SOLVE)[0] for p in solve)
    owners = {owner(p) for p in names}
    assert {BLOCK_SOLVE, RECURRENCE} <= owners


@pytest.mark.parametrize("driver", ["sstep_dcd", "dcd"])
def test_dcd_programs_carry_no_block_solve(driver):
    names = _op_names(driver)
    assert names and not any(BLOCK_SOLVE in p for p in names)


def _solve_span(problem, **opt_kw):
    A, y = _problem()
    tel = Telemetry()
    opts = SolverOptions(seed=7, telemetry=tel, **opt_kw)
    if problem == "krr":
        res = KernelRidge(lam=0.5, kernel="rbf", options=opts).fit(A, y)
    else:
        res = KernelSVM(C=1.0, kernel="rbf", options=opts).fit(
            A, jnp.sign(y) + (y == 0))
    (span,) = [sp for sp in tel.spans if sp.name == "solve"]
    return span, res


@pytest.mark.parametrize("case,problem,kw,b,s", [
    ("krr_fast", "krr", dict(method="sstep", s=4, b=4, max_iters=40), 4, 4),
    ("krr_tol", "krr", dict(method="sstep", s=4, b=2, max_iters=64,
                            tol=1e-12, check_every=2), 2, 4),
    ("krr_classical", "krr", dict(method="classical", b=3, max_iters=10),
     3, 1),
    ("ksvm_fast", "ksvm", dict(method="sstep", s=4, max_iters=32), 1, 4),
])
def test_solve_span_counts_block_solves(case, problem, kw, b, s):
    """A K-RR round runs one b x b solve per step, so the fit's count is
    the span's ``s`` times ``rounds_run``; K-SVM's ``b`` is 1."""
    span, res = _solve_span(problem, **kw)
    assert span.args["path"] == ("tol" if "tol" in kw else "fast")
    assert span.args["b"] == b and span.args["s"] == s
    assert res.rounds_run == -(-res.iters_run // span.args["s"])


def test_sstep_bdcd_matches_the_plain_reference():
    """s = 16, b = 8 as in ``krr-msd``: alpha after 16 rounds against
    classical BDCD from the paper's definitions at ``HIGHEST``, on the
    schedule the facade drew, to 1e-5 in float32."""
    sys.path.insert(0, str(ROOT))
    from bench import reference
    m, n, H, lam, sigma = 512, 16, 256, 0.1, 1.0
    A, y = _problem(m, n, seed=3)
    res = KernelRidge(lam=lam, kernel=KernelConfig("rbf", sigma=sigma),
                      options=SolverOptions(
                          method="sstep", s=16, b=8, max_iters=H,
                          seed=11)).fit(A, y)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1],
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    ref = reference.krr_bdcd(A, y, res.schedule, lam=lam, sigma=sigma, s=16,
                             dtype=jnp.float32, mesh=mesh)
    got, want = np.asarray(res.alpha, np.float64), np.asarray(ref, np.float64)
    assert res.schedule.shape == (H, 8)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-5

