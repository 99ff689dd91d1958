"""Readings of the program's own tracing on the chip, in one process.

    python3 bench/tracing.py --workload ksvm-covtype.fit --seeds 6 --fits 3
    python3 bench/tracing.py --record-trace DIR

The first form measures what telemetry costs when it is on: for each of
``--seeds`` seeds (``--seed0``, ``--seed0 + 1``, ...) it makes the cell's
data, warms up, then times ``--fits`` fits with ``telemetry=None`` and as
many with ``telemetry=Telemetry()``, alternating, with no profiler
running.  One JSON line per seed (mean ``fit_s`` of each), then a
summary: the median over seeds of the on/off ratio.

``--record-trace DIR`` records a small profiler trace of one scoped K-SVM
fit (m = 65,536, n = 54, s = 16, 4 rounds, telemetry on) inside a
``bench.window`` annotation, and writes ``DIR/scoped_fit.xplane.pb``: the
same trace without its ``/host:metadata`` plane (the programs' HLO, which
no reader uses and which is most of its size).
``tests/bench_tests/data/scoped_fit.xplane.pb`` was recorded so on a TPU
v5e, for the readers' tests.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run as bench_run  # noqa: E402

RECORDED = dict(m=65536, n=54, s=16, max_iters=64)


def cost(args) -> None:
    from bench import cells, spec
    from repro.obs import Telemetry

    cell = spec.load_cell(args.workload)
    devices = bench_run.chips_or_exit(cell.chips)
    ratios = []
    for k in range(args.seeds):
        seed = args.seed0 + k
        driver = cells.DRIVERS[cell.kind](cell, seed, devices)
        driver.warm()
        driver.fit(1, Telemetry())           # warm the traced path too
        times = {"off": [], "on": []}
        for i in range(args.fits):
            for mode in ("off", "on") if i % 2 == 0 else ("on", "off"):
                f = driver.fit(2 + 2 * i + (mode == "on"),
                               Telemetry() if mode == "on" else None)
                times[mode].append(f.t1 - f.t0)
        row = {"seed": seed,
               "fit_s_off": statistics.fmean(times["off"]),
               "fit_s_on": statistics.fmean(times["on"])}
        row["on_over_off"] = row["fit_s_on"] / row["fit_s_off"]
        ratios.append(row["on_over_off"])
        print(json.dumps(row), flush=True)
        driver.free()
    print(json.dumps({"median_on_over_off": statistics.median(ratios),
                      "min": min(ratios), "max": max(ratios)}))


def _varint(buf: bytes, i: int):
    shift = value = 0
    while True:
        b = buf[i]
        value |= (b & 0x7F) << shift
        i += 1
        shift += 7
        if b < 0x80:
            return value, i


def without_plane(src: str, dst: str, name: str) -> None:
    """Copy an xplane file, leaving out the plane called ``name``.  An
    ``XSpace`` is a run of length-delimited fields; planes are field 1,
    and a plane's name is its field 2."""
    buf = Path(src).read_bytes()
    out, i = bytearray(), 0
    while i < len(buf):
        start = i
        tag, i = _varint(buf, i)
        size, i = _varint(buf, i)       # every XSpace field is delimited
        body, i = buf[i:i + size], i + size
        if tag >> 3 == 1:
            j, plane_name = 0, None
            while j < len(body) and plane_name is None:
                ftag, j = _varint(body, j)
                if ftag & 7 == 0:
                    _, j = _varint(body, j)
                    continue
                fsize, j = _varint(body, j)
                if ftag >> 3 == 2:
                    plane_name = body[j:j + fsize].decode()
                j += fsize
            if plane_name == name:
                continue
        out += buf[start:i]
    Path(dst).write_bytes(bytes(out))


def record_trace(out: str) -> None:
    import jax
    import jax.numpy as jnp
    import jax.profiler as jp

    from bench import devtrace
    from repro.api import KernelSVM, SolverOptions
    from repro.obs import Telemetry

    bench_run.chips_or_exit(1)
    m, n, s, iters = (RECORDED[k] for k in ("m", "n", "s", "max_iters"))
    k1, k2 = jax.random.split(jax.random.key(0))
    A = jax.random.normal(k1, (m, n), jnp.float32) / n ** 0.5
    y = jnp.where(jax.random.bernoulli(k2, 0.5, (m,)), 1.0, -1.0)

    def fit(tel):
        opts = SolverOptions(method="sstep", s=s, max_iters=iters,
                             telemetry=tel)
        return KernelSVM(C=1.0, kernel="rbf", options=opts).fit(A, y)

    jax.block_until_ready(fit(None).alpha)
    opts = jp.ProfileOptions()
    opts.python_tracer_level = 0
    with jp.trace(out, profiler_options=opts):
        with jp.TraceAnnotation("bench.window"):
            with jp.TraceAnnotation("bench.fit"):
                res = fit(Telemetry())
                jax.block_until_ready(res.alpha)
    path = str(Path(out) / "scoped_fit.xplane.pb")
    without_plane(devtrace.find_xplane(out), path, "/host:metadata")
    tr = devtrace.load(path)
    print("trace", path, Path(path).stat().st_size, "bytes,",
          sum(len(v) for v in tr.ops.values()), "device ops, rounds",
          res.rounds_run)
    print("repro spans", [(nm, b - a) for nm, a, b in tr.host
                          if nm.startswith("repro.")])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--seed0", type=int, default=1)
    ap.add_argument("--fits", type=int, default=3)
    ap.add_argument("--record-trace", metavar="DIR")
    args = ap.parse_args(argv)
    bench_run.use_bench_cache()
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    if args.record_trace:
        record_trace(args.record_trace)
    else:
        cost(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
