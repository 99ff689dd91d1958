"""Readings for a cell's limits: the program's and the control's, in one process.

    python3 bench/calibrate.py --workload krr-msd.fit --seeds 12 --control 3

For each of ``--seeds`` seeds (``--seed0``, ``--seed0 + 1``, ...) it runs
``bench/run.py``'s own timed sequence (``run.session``: data, warm-up,
the cell's traffic for ``--seconds`` at the cell's sizes and load) and
reads the numbers the check compares; for the first ``--control`` seeds
it also reads them with the control (the reference in bfloat16) in the
program's place.  ``--fault <name>`` plants one of ``bench/faults.py``'s
faults under the timed path first.  One JSON line per seed, then a summary: the largest
program reading and the smallest control reading of each number, from
which ``bench/limits/<cell>.json`` is set.

``--record-trace DIR`` instead records a small profiler trace of a few
KMV calls inside a ``bench.window`` annotation and prints its reduction:
``tests/bench_tests/data/small_trace.xplane.pb`` was recorded so on a
TPU v5e, for the reducers' tests.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import faults  # noqa: E402
from bench import run as bench_run  # noqa: E402


def calibrate(args) -> None:
    import jax

    from bench import faults, spec

    cell = spec.load_cell(args.workload)
    devices = bench_run.chips_or_exit(cell.chips)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    if args.fault:
        faults.plant(args.fault)
        jax.clear_caches()
    n_check = cell.limits["n_check"]
    prog, ctrl = {}, {}
    for i in range(args.seeds):
        seed = args.seed0 + i
        t0 = time.perf_counter()
        s = bench_run.session(cell, seed, args.seconds, False, devices, t0)
        t2 = time.perf_counter()
        got = s.driver.check(n_check)
        t3 = time.perf_counter()
        row = {"seed": seed, "fault": args.fault, "program": got,
               "failed": s.out["failed"], "attempted": s.out["attempted"],
               "metrics": s.out["metrics"], "setup_s": s.setup_s,
               "check_s": t3 - t2, "memory_peak_bytes": s.memory_peak_bytes,
               "notes": s.ctx.notes}
        for k, v in got.items():
            prog[k] = max(prog.get(k, v), v)
        if i < args.control:
            c = s.driver.check(n_check, control=True)
            row["control"] = c
            row["control_s"] = time.perf_counter() - t3
            for k, v in c.items():
                ctrl[k] = min(ctrl.get(k, v), v)
        print(json.dumps(row), flush=True)
        del s
    print(json.dumps({"workload": args.workload, "fault": args.fault,
                      "program_max": prog, "control_min": ctrl}), flush=True)


def record_trace(out: str) -> None:
    import jax
    import jax.numpy as jnp
    import jax.profiler as jp

    from bench import devtrace

    bench_run.chips_or_exit(1)
    A = jax.random.normal(jax.random.key(0), (65536, 90), jnp.float32)
    B = A[:128]

    @jax.jit
    def kmv(A, B, x):
        d = A @ B.T
        sq = (jnp.sum(A * A, 1)[:, None] + jnp.sum(B * B, 1)[None, :]
              - 2 * d)
        return jnp.exp(-jnp.maximum(sq, 0)).T @ x

    x = jnp.ones((65536,), jnp.float32)
    jax.block_until_ready(kmv(A, B, x))
    opts = jp.ProfileOptions()
    opts.python_tracer_level = 0
    with jp.trace(out, profiler_options=opts):
        with jp.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jp.TraceAnnotation("bench.fit"):
                    jax.block_until_ready(kmv(A, B, x))
                time.sleep(0.002)
    path = devtrace.find_xplane(out)
    print("trace", path)
    tr = devtrace.load(path)
    lo, hi = tr.annotation("bench.window")
    print("window", lo, hi, "busy", devtrace.busy_s(tr, lo, hi),
          "idle%", devtrace.idle_pct(tr, lo, hi))
    print(json.dumps(devtrace.breakdown(
        tr, lo, hi, [e for e in tr.host if e[0].startswith("bench.")])))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--seed0", type=int, default=1)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS),
                    help="read the numbers with this fault planted "
                         "(bench/faults.py)")
    ap.add_argument("--record-trace", metavar="DIR")
    args = ap.parse_args(argv)
    bench_run.use_bench_cache()
    if args.record_trace:
        record_trace(args.record_trace)
    else:
        calibrate(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
