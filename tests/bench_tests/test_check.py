"""The benchmark's correctness check, run on the CPU at a small size.

Each cell's check must pass the program, fail the control (the plain
reference computed in bfloat16 in the program's place), and fail the
program with each fault the cell can have planted under the timed path:
a fit that returns its state unchanged, a KMV that sums half of the rows
and doubles the result, a KMV that returns zeros, answers altered where
they are produced (``bench/faults.py``).  The
runs skip the command's look for a chip and drive the rest of
``bench.run.run`` (set-up, window, check) with the cell's own limits.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402

FIT_CELLS = ["krr-msd.fit", "ksvm-covtype.fit"]
SERVE_CELLS = ["krr-msd.serve"]
SEED = 3_000_000_011


# cells whose files are kept while BENCHMARK.json has no entry for them
# (PERF.md, Open questions): their (config, traffic)
UNLISTED = {"krr-msd.fit": ("krr-msd", "fit-fixed"),
            "krr-msd.serve": ("krr-msd", "serve-open")}


def load(name):
    if name not in UNLISTED:
        return spec.load_cell(name)
    import json
    config, traffic = UNLISTED[name]

    def read(path):
        with open(spec.BENCH / path) as f:
            return json.load(f)
    return spec.Cell(name=name, chips=1,
                     config=read(f"configs/{config}.json"),
                     traffic=read(f"traffic/{traffic}.json"),
                     limits=read(f"limits/{name}.json"),
                     end_to_end=[], per_layer=[])


def small(name):
    cell = load(name)
    cell.config.update(m=2048)
    cell.config["options"]["max_iters"] = 128
    if cell.kind == "serve":
        cell.traffic.update(rate_rps=40, pool_rows=1024)
    return cell


def run(cell, seconds=0.5):
    import jax

    from bench import run as bench_run
    return bench_run.run(cell, SEED, seconds, False, jax.devices()[:1],
                         time.perf_counter())


def failing(result):
    return [k for k, v in result["checks"].items() if v["value"] > v["limit"]]


@pytest.mark.parametrize("name", FIT_CELLS + SERVE_CELLS)
def test_program_passes(name):
    res = run(small(name))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1


@pytest.mark.parametrize("name", FIT_CELLS + SERVE_CELLS)
def test_control_fails(name):
    import jax

    from bench import cells
    cell = small(name)
    driver = cells.DRIVERS[cell.kind](cell, SEED, jax.devices()[:1])
    driver.warm()
    driver.window(0.5, traced=False)
    driver.free()
    got = driver.check(cell.limits["n_check"], control=True)
    limits = {k: v["limit"] for k, v in cell.limits["checks"].items()}
    assert any(got[k] > limits[k] for k in limits), (got, limits)


# each cell's faults, by test id: the name of the fault in bench/faults.py
FAULTS = {"unchanged": "unchanged", "half_rows": "half_rows",
          "zero_kmv": "zero_kmv", "altered": "altered_fit"}
SERVE_FAULTS = {"half_rows": "half_rows", "zero_kmv": "zero_kmv",
                "altered": "altered_serve"}


@pytest.mark.parametrize("name,fault",
                         [(c, f) for c in FIT_CELLS for f in FAULTS]
                         + [(c, f) for c in SERVE_CELLS for f in SERVE_FAULTS])
def test_fault_fails_the_check(name, fault):
    import jax

    from bench import faults
    jax.clear_caches()           # the patched program must be traced anew
    undo = faults.plant((FAULTS if name in FIT_CELLS else SERVE_FAULTS)[fault])
    try:
        res = run(small(name))
    finally:
        undo()
        jax.clear_caches()
    assert not res["correct"]
    assert failing(res), res["checks"]


# --- the four-chip cell, on four virtual CPU devices in a child process ---

FOUR_CHIP_SCRIPT = r'''
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
from bench import cells, spec
from bench import run as bench_run
import repro.api as api
import repro.core.distributed as distributed

SEED = 3000000013
devices = jax.devices()[:4]

def small():
    # krr-mnist8m has no cell yet (PERF.md, Open questions): its config
    # and mix run here under the K-RR fit cell's limits
    root = spec.ROOT
    cell = spec.Cell(
        name="krr-mnist8m.fit-2d", chips=4,
        config=json.load(open(root / "bench/configs/krr-mnist8m.json")),
        traffic=json.load(open(root / "bench/traffic/fit-fixed.json")),
        limits=json.load(open(root / "bench/limits/krr-msd.fit.json")),
        end_to_end=[], per_layer=[])
    cell.config.update(m=4096, n=64)
    cell.config["options"]["max_iters"] = 128
    return cell

def run():
    return bench_run.run(small(), SEED, 0.5, False, devices,
                         time.perf_counter())

def bad(res):
    return not res["correct"] and any(
        v["value"] > v["limit"] for v in res["checks"].values())

out = {"program": run()["correct"]}
cell = small()
d = cells.FitDriver(cell, SEED, devices)
d.warm(); d.window(0.5, traced=False); d.free()
got = d.check(cell.limits["n_check"], control=True)
out["control"] = any(got[k] > v["limit"]
                     for k, v in cell.limits["checks"].items())

class NoExchangeLax:
    def __getattr__(self, k):
        return getattr(jax.lax, k)
    @staticmethod
    def psum(x, axis_name):
        return x

class NoExchangeJax:
    lax = NoExchangeLax()
    def __getattr__(self, k):
        return getattr(jax, k)

distributed.jax = NoExchangeJax()
jax.clear_caches()
out["no_exchange"] = bad(run())
distributed.jax = jax
real = api._dist_chunk
api._dist_chunk = lambda A, y, a0, *a, **k: a0
jax.clear_caches()
out["unchanged"] = bad(run())
api._dist_chunk = real
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def four_chip_outcomes():
    import json
    import os
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", FOUR_CHIP_SCRIPT, str(ROOT)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", ["program", "control", "no_exchange",
                                  "unchanged"])
def test_four_chip_cell_check(four_chip_outcomes, case):
    """The program passes; the control, a 2d fit whose collectives are
    left out, and one that returns its state unchanged all fail."""
    assert four_chip_outcomes[case] is True
