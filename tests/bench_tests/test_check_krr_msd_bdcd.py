"""The check of ``krr-msd.fit-bdcd``, run on the CPU at a small size.

The cell's own files (``BENCHMARK.json``, ``bench/limits/
krr-msd.fit-bdcd.json``) at m = 2,048 and 128 block iterations: the
program passes; the control (the plain reference in bfloat16 in the
program's place) fails; and so does the program with each fault of
``bench/faults.py`` that a fit can have planted under the timed path.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402

CELL = "krr-msd.fit-bdcd"
SEED = 3_000_000_017
FAULTS = ("unchanged", "half_rows", "zero_kmv", "altered_fit")


def small():
    cell = spec.load_cell(CELL)
    cell.config.update(m=2048)
    cell.config["options"]["max_iters"] = 128
    return cell


def checks(case):
    """The numbers compared and whether the run was judged correct."""
    import jax

    from bench import cells, faults
    from bench import run as bench_run
    cell = small()
    devices = jax.devices()[:1]
    if case == "control":
        driver = cells.DRIVERS[cell.kind](cell, SEED, devices)
        driver.warm()
        driver.window(0.5, traced=False)
        driver.free()
        got = driver.check(cell.limits["n_check"], control=True)
        limits = {k: v["limit"] for k, v in cell.limits["checks"].items()}
        return got, all(got[k] <= limits[k] for k in limits)
    undo = None
    if case in FAULTS:
        jax.clear_caches()       # the patched program must be traced anew
        undo = faults.plant(case)
    try:
        res = bench_run.run(cell, SEED, 0.5, False, devices,
                            time.perf_counter())
    finally:
        if undo is not None:
            undo()
            jax.clear_caches()
    assert res["failed"] == 0 and res["attempted"] >= 1
    return ({k: v["value"] for k, v in res["checks"].items()},
            res["correct"])


@pytest.mark.parametrize("case", ("program", "control") + FAULTS)
def test_check_separates_program_from_control_and_faults(case):
    got, correct = checks(case)
    assert correct is (case == "program"), got
    assert got["schedule_mismatch"] == 0
