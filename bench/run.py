"""The benchmark's command: one run of one cell on the chips it is started on.

    python3 bench/run.py --workload krr-msd.fit --seed 7 --seconds 10 --trace 0

Reads the cell from ``BENCHMARK.json`` and its files (``bench/spec.py``),
makes its data from ``--seed`` on the device, warms up every shape the
window uses (set-up), drives the traffic for ``--seconds``, reads the
peak device memory, frees the program's state, and checks a sample of
what the window produced against the plain reference
(``bench/reference.py``) within the cell's limits
(``bench/limits/<cell>.json``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs
the window under the JAX profiler and reports its per-layer metrics,
each read by ``bench/layers/<metric>.py``, with the device's busy time
and the trace's breakdown.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, [``breakdown``],
``checks``); the numbers compared are also the last lines of standard
error.  Without a TPU, with fewer chips than the cell asks for, or under
``REPRO_SANITIZE=1`` (which forces Pallas interpret mode), it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE = ROOT / ".bench_cache"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def use_bench_cache() -> None:
    """Keep JAX's compile cache, and the TPU runtime's logs (else under
    /tmp), in the checkout at fixed paths; call before JAX is imported.
    The program's use_compile_cache() takes the directory named here."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    os.environ["TPU_LOG_DIR"] = str(CACHE / "tpu_logs")


def chips_or_exit(chips: int):
    """The cell's devices, or exit 2 when this is not a TPU run."""
    if os.environ.get("REPRO_SANITIZE", "") == "1":
        print("bench: REPRO_SANITIZE=1 forces Pallas interpret mode; the "
              "benchmark measures the chip path only", file=sys.stderr)
        raise SystemExit(2)
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"bench: JAX finds no device: {e}", file=sys.stderr)
        raise SystemExit(2)
    if devs[0].platform != "tpu":
        print(f"bench: no TPU (JAX's default device is "
              f"{devs[0].platform!r}); the benchmark runs on the chip only",
              file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} chips, JAX sees {len(devs)}",
              file=sys.stderr)
        raise SystemExit(2)
    return devs[:chips]


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


@contextlib.contextmanager
def watched(notes: list):
    """Count what the process does besides the work inside the window:
    JAX programs traced and built (compiled or loaded from the cache),
    and Python's garbage collections with their pauses; one line goes
    to ``notes``."""
    import gc

    import jax.monitoring as monitoring

    ev = {"traced": 0, "built": 0, "build_s": 0.0}
    pauses, started = [], []

    def on_event(name, secs, **_):
        if name == "/jax/core/compile/jaxpr_trace_duration":
            ev["traced"] += 1
        elif name == "/jax/core/compile/backend_compile_duration":
            ev["built"] += 1
            ev["build_s"] += secs

    def on_gc(phase, info):
        if phase == "start":
            started[:] = [time.perf_counter()]
        elif started:
            pauses.append((time.perf_counter() - started.pop(),
                           info["generation"]))

    monitoring.register_event_duration_secs_listener(on_event)
    gc.callbacks.append(on_gc)
    try:
        yield
    finally:
        gc.callbacks.remove(on_gc)
        monitoring.unregister_event_duration_listener(on_event)
        worst = max(pauses, default=(0.0, None))
        notes.append(
            f"in the window: {ev['traced']} programs traced, {ev['built']} "
            f"built ({ev['build_s']:.3f} s); {len(pauses)} garbage "
            f"collections, {sum(p for p, _ in pauses):.3f} s, longest "
            f"{worst[0]:.3f} s (generation {worst[1]})")


@dataclasses.dataclass
class Session:
    """What one run's timed sequence leaves: the driver (with what the
    window produced), the window's outcome, the set-up time, the peak
    device memory and, in a traced run, the readers' context."""
    driver: object
    out: dict
    setup_s: float
    memory_peak_bytes: int
    ctx: "Context"


def session(cell, seed: int, seconds: float, trace: bool, devices,
            t_start: float) -> Session:
    """The sequence every run times, and the one the limits and the knee
    are read through: make the data and the program's state from the
    seed, warm up (set-up, up to the window's start), drive the window,
    read the peak device memory and free the program's state."""
    import gc

    import jax.profiler as jp

    from bench import cells, spec

    t_init = time.perf_counter()
    driver = cells.DRIVERS[cell.kind](cell, seed, devices)
    t_data = time.perf_counter()
    driver.warm()
    t_warm = time.perf_counter()
    readers = {m["name"]: spec.layer_reader(m["name"])
               for m in cell.per_layer} if trace else {}
    ctx = Context(cell=cell, driver=driver, devices=devices, readers=readers)
    ctx.notes.append(f"set-up: {t_init - t_start:.3f} s to the devices, "
                     f"{t_data - t_init:.3f} s data, "
                     f"{t_warm - t_data:.3f} s warm-up")
    # the set-up's objects (imports, traced programs) never die: kept out
    # of the collector's scans, they add no pause to the window's
    gc.collect()
    gc.freeze()
    if trace:
        tdir = CACHE / "trace"
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jp.ProfileOptions()
        opts.python_tracer_level = 0
        setup_s = time.perf_counter() - t_start
        # a profile holds some 6.3M device operations, and drops the rest:
        # the traced window is as long as the mix's trace_seconds
        traced = min(seconds, cell.traffic.get("trace_seconds", seconds))
        with jp.trace(str(tdir), profiler_options=opts):
            with watched(ctx.notes), jp.TraceAnnotation("bench.window"):
                out = driver.window(traced, traced=True)
            for name, mod in readers.items():
                if hasattr(mod, "probe"):
                    with jp.TraceAnnotation(f"bench.probe.{name}"):
                        ctx.probes[name] = mod.probe(ctx)
    else:
        setup_s = time.perf_counter() - t_start
        with watched(ctx.notes):
            out = driver.window(seconds, traced=False)
    gc.unfreeze()
    mem = memory_peak(devices)
    driver.free()
    return Session(driver, out, setup_s, mem, ctx)


def run(cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float) -> dict:
    """One run of ``cell``; returns the result object."""
    import jax

    from bench import devtrace, spec

    s = session(cell, seed, seconds, trace, devices, t_start)
    driver, out, ctx, mem = s.driver, s.out, s.ctx, s.memory_peak_bytes
    checks = driver.check(cell.limits["n_check"])
    limits = {k: v["limit"] for k, v in cell.limits["checks"].items()}
    correct = (all(checks[k] <= limits[k] for k in limits)
               and out["failed"] == 0)

    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": mem}
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"]}
    if trace:
        t_parse = time.perf_counter()
        xplane = devtrace.find_xplane(str(CACHE / "trace"))
        ctx.trace = devtrace.load(xplane)
        ctx.notes.append(
            f"trace: {os.path.getsize(xplane)} bytes, "
            f"{sum(len(v) for v in ctx.trace.ops.values())} device ops on "
            f"{len(ctx.trace.ops)} devices, {len(ctx.trace.host)} host "
            f"events, read in {time.perf_counter() - t_parse:.1f} s")
        ctx.window = ctx.trace.annotation("bench.window")
        ctx.peaks = spec.peaks(devices[0].device_kind)
        lo, hi = ctx.window
        metrics = {}
        for m in cell.per_layer:
            v = ctx.readers[m["name"]].read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        dev["busy_s"] = devtrace.busy_s(ctx.trace, lo, hi)
        dev["window_s"] = hi - lo
        labels = [e for e in ctx.trace.host if e[0].startswith("bench.")]
        result["metrics"] = metrics
        result["device"] = dev
        result["breakdown"] = devtrace.breakdown(ctx.trace, lo, hi, labels)
        shutil.rmtree(CACHE / "trace", ignore_errors=True)
    else:
        metrics = {"setup_s": {"value": s.setup_s, "unit": "s"}}
        for m in cell.end_to_end:
            if m["name"] in out["metrics"]:
                metrics[m["name"]] = {"value": out["metrics"][m["name"]],
                                      "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = dev
    for line in driver_notes(driver) + ctx.notes:
        print(line, file=sys.stderr)
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                        for k in limits}
    return result


class Context:
    """What a per-layer reader sees: the cell, the driver (its fits or
    requests, the program's telemetry spans), the parsed trace, the
    traced window on the trace's clock, the peak row of the device and
    what each probe returned."""

    def __init__(self, cell, driver, devices, readers):
        self.cell = cell
        self.driver = driver
        self.devices = devices
        self.readers = readers
        self.probes = {}
        self.trace = None
        self.window = None
        self.peaks = None
        self.notes = []


def driver_notes(driver) -> list:
    lag = getattr(driver, "lag", None)
    if lag is None or not len(lag):
        return []
    import numpy as np
    return [f"generator lag: p50 {np.percentile(lag, 50) * 1e3:.3f} ms, "
            f"p95 {np.percentile(lag, 95) * 1e3:.3f} ms, "
            f"max {lag.max() * 1e3:.3f} ms over {len(lag)} requests"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    use_bench_cache()

    from bench import spec

    cell = spec.load_cell(args.workload)
    devices = chips_or_exit(cell.chips)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices,
                 T_START)
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
