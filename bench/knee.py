"""Sweep the serve mix's offered rate on the chip, in one process, to find
the knee: the highest rate served with no growing backlog.

    python3 bench/knee.py --workload krr-msd.serve --rates 600,800,1000 --seconds 8

For each rate it runs ``bench/run.py``'s own timed sequence
(``run.session``: data, warm-up, the cell's serve traffic for
``--seconds``, with the same engine and generator) and prints one JSON
line: p50/p95 latency, rows served per second in the window, how long the
queue took to drain after the window closed (``drain_s``, near 0 while the
engine keeps up, growing with the window when it does not) and the
generator's lag.  The knee and the rate chosen from it are recorded in
the mix's file (``bench/traffic/<mix>.json``) with the commit measured.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench_run.use_bench_cache()

    from bench import spec

    cell = spec.load_cell(args.workload)
    devices = bench_run.chips_or_exit(cell.chips)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic["rate_rps"] = rate
        t0 = time.perf_counter()
        s = bench_run.session(cell, args.seed, args.seconds, False, devices,
                              t0)
        driver, out = s.driver, s.out
        print(json.dumps({
            "rate_rps": rate, "attempted": out["attempted"],
            "failed": out["failed"],
            "p50_ms": out["metrics"]["serve_p50_ms"],
            "p95_ms": driver.p95_ms,
            "rows_per_s": out["metrics"]["serve_rows_per_s"],
            "offered_rows_per_s": sum(int(r) for _, r, _ in driver.requests)
            / args.seconds,
            "drain_s": driver.drain_s,
            "lag_p95_ms": float(np.percentile(driver.lag, 95)) * 1e3,
            "notes": s.ctx.notes}), flush=True)
        del s, driver
    return 0


if __name__ == "__main__":
    sys.exit(main())
