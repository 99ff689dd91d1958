"""``python -m repro.obs`` — record and export telemetry from
self-contained demo workloads (mirrors the ``repro.analysis`` CLI).

    python -m repro.obs trace --out t.json  # solve + serving window -> trace
    python -m repro.obs scrape            # serving drive -> Prometheus text

Every subcommand fits/serves a small synthetic problem with telemetry
enabled, so the tooling is demonstrable with zero setup; pass --m/--iters
to scale the demo.
"""
from __future__ import annotations

import argparse
import os
import sys


def _demo_fit(m: int, iters: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import KernelRidge, SolverOptions
    from repro.obs import Telemetry

    key = jax.random.key(0)
    A = jax.random.normal(key, (m, 16), jnp.float32)
    rng = np.random.default_rng(0)
    y = jnp.asarray(np.asarray(A) @ rng.standard_normal(16), A.dtype)
    tel = Telemetry()
    opts = SolverOptions(method="sstep", s=8, b=8, tol=1e-8,
                         check_every=4, max_iters=iters, guard=True,
                         recompute_every=8, telemetry=tel)
    kr = KernelRidge(lam=1.0, kernel="rbf", options=opts)
    result = kr.fit(A, y)
    return result, tel


def _demo_serve(m: int, iters: int, tickets: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import KernelRidge, SolverOptions
    from repro.obs import Telemetry
    from repro.serve import ModelRegistry, ServingEngine

    key = jax.random.key(1)
    A = jax.random.normal(key, (m, 16), jnp.float32)
    rng = np.random.default_rng(1)
    y = jnp.asarray(np.asarray(A) @ rng.standard_normal(16), A.dtype)
    kr = KernelRidge(lam=1.0, kernel="rbf",
                     options=SolverOptions(method="sstep", s=8, b=8,
                                           max_iters=iters))
    kr.fit(A, y)
    reg = ModelRegistry(predict_batch=32)
    reg.register("krr", kr)
    tel = Telemetry()
    engine = ServingEngine(reg, slots=32, telemetry=tel)
    engine.warmup()
    Q = np.asarray(jax.random.normal(jax.random.key(2), (tickets, 16),
                                     jnp.float32))
    for i in range(tickets):
        engine.submit("krr", Q[i])
        if (i + 1) % 8 == 0:
            engine.step()
    engine.run_until_idle()
    return engine, tel


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.obs",
        description="telemetry demos: Perfetto trace, Prometheus scrape")
    # shared demo knobs live on a parent so they parse AFTER the
    # subcommand too (python -m repro.obs trace --m 256)
    shared = argparse.ArgumentParser(add_help=False)
    shared.add_argument("--m", type=int, default=192,
                        help="demo problem rows")
    shared.add_argument("--iters", type=int, default=256,
                        help="demo solve iteration budget")
    sub = ap.add_subparsers(dest="cmd", required=True)
    p_trace = sub.add_parser("trace", parents=[shared],
                             help="record a solve + serving window, "
                                  "export Chrome trace")
    p_trace.add_argument("--out", default="repro_trace.json",
                         help="output trace path")
    p_scrape = sub.add_parser("scrape", parents=[shared],
                              help="serving drive -> Prometheus text "
                                   "exposition")
    p_scrape.add_argument("--tickets", type=int, default=64)
    args = ap.parse_args(argv)

    if args.cmd == "trace":
        from repro.obs.export import save_trace
        _result, tel = _demo_fit(args.m, args.iters)
        engine, stel = _demo_serve(args.m, args.iters, tickets=32)
        # both windows ride one trace: merge the serving log into the
        # solve handle (timestamps share the perf_counter clock)
        tel.spans.extend(stel.spans)
        tel.marks.extend(stel.marks)
        path = save_trace(os.path.abspath(args.out), tel)
        print(f"wrote {path} ({len(tel.spans)} spans, "
              f"{len(tel.marks)} marks) — open in ui.perfetto.dev")
        return 0

    # scrape
    engine, tel = _demo_serve(args.m, args.iters, tickets=args.tickets)
    sys.stdout.write(tel.metrics.to_prometheus_text())
    return 0


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    sys.exit(main())
