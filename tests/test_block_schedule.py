"""The K-RR block schedule's draw (``core.bdcd.block_schedule``).

  * its bits are the plain reference's (``bench/reference.py``, Floyd's
    algorithm written out) over seeds and shapes, b = 1 and b = m among
    them;
  * it is one cached program per (H, m, b): draws with fresh keys at a
    shape seen before build nothing, which the facade's ``schedule``
    span reports as its ``compiles`` arg;
  * a b outside [1, m] raises ValueError before anything compiles;
  * the fleet solver and the facade draw the same schedule for a seed.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import KernelRidge, SolverOptions
from repro.core import block_schedule
from repro.obs import Telemetry
from repro.obs.spans import programs_built

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import reference  # noqa: E402


def _problem(m, n=4, seed=0):
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    y = jnp.asarray(np.asarray(A) @ rng.standard_normal(n), jnp.float32)
    return A, y


def _draw_counted(key, H, m, b):
    built = {}
    with programs_built(built):
        sched = block_schedule(key, H, m, b).block_until_ready()
    return sched, built["compiles"]


@pytest.mark.parametrize("seed,H,m,b", [
    (0, 64, 48, 8),
    (1, 37, 101, 5),           # H not a multiple of s = 4 or 16
    (2, 50, 40, 1),            # b = 1
    (3, 12, 8, 8),             # b = m: every block is a permutation
    (4, 1, 2, 2),
    (2 ** 31 + 17, 33, 463, 8),  # a seed past 32 signed bits
])
def test_draw_matches_reference_bitwise(seed, H, m, b):
    got = np.asarray(block_schedule(jax.random.key(seed), H, m, b))
    want = np.asarray(reference.block_schedule(seed, H, m, b))
    assert got.shape == (H, b) and got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)
    assert (np.sort(got, axis=1)[:, 1:] != np.sort(got, axis=1)[:, :-1]).all()
    assert got.min() >= 0 and got.max() < m


def test_one_program_per_shape():
    keys = [jax.random.key(100 + i) for i in range(5)]
    counts = []
    scheds = []
    for k in keys:
        sched, n = _draw_counted(k, 29, 83, 6)
        counts.append(n)
        scheds.append(np.asarray(sched))
    assert counts == [1, 0, 0, 0, 0]
    # the key is traced, not baked in: each key draws its own blocks
    assert len({s.tobytes() for s in scheds}) == 5
    # another shape is another program
    assert _draw_counted(keys[0], 29, 83, 5)[1] == 1


@pytest.mark.parametrize("b", [0, 84])
def test_bad_block_size_raises_before_compiling(b):
    built = {}
    with programs_built(built):
        with pytest.raises(ValueError, match="block size"):
            block_schedule(jax.random.key(0), 29, 83, b)
    assert built["compiles"] == 0


@pytest.mark.parametrize("method,s", [("sstep", 4), ("classical", 1)])
def test_second_facade_fit_builds_no_schedule(method, s):
    m = 52 if method == "sstep" else 56     # a shape of this test's own
    A, y = _problem(m)
    compiles = []
    for seed in range(3):
        tel = Telemetry()
        KernelRidge(lam=0.5, kernel="rbf", options=SolverOptions(
            method=method, s=s, b=4, max_iters=40, seed=seed,
            telemetry=tel)).fit(A, y)
        span, = [sp for sp in tel.spans if sp.name == "schedule"]
        compiles.append(span.args["compiles"])
        # only the schedule span counts programs
        assert all("compiles" not in sp.args for sp in tel.spans
                   if sp.name != "schedule")
    assert compiles == [1, 0, 0]


def test_fleet_draws_the_facades_schedule(monkeypatch):
    from repro.tune import fleet, solve_fleet
    A, y = _problem(60)
    drawn = []

    def spy(key, H, m, b):
        drawn.append(_draw_counted(key, H, m, b))
        return drawn[-1][0]

    monkeypatch.setattr(fleet, "block_schedule", spy)
    opts = SolverOptions(method="sstep", s=4, b=4, max_iters=48, seed=11)
    res = KernelRidge(lam=1.0, kernel="rbf", options=opts).fit(A, y)
    solve_fleet(A, y, lams=(0.5, 1.0), kernel="rbf", options=opts)
    (sched, compiles), = drawn
    assert compiles == 0        # the facade's fit built the program
    np.testing.assert_array_equal(np.asarray(sched),
                                  np.asarray(res.schedule))
