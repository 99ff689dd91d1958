"""Benchmark harness: one entry per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (see common.emit).

  fig1   DCD vs s-step DCD convergence (duality gap)        [paper Fig 1]
         (each s-step record carries a ``fit`` block: repro.api FitResult
          wall-clock + Hockney-modeled comm words/msgs/time)
  fig2   BDCD vs s-step BDCD convergence (rel. error)       [paper Fig 2]
         (``fit`` blocks as in fig1)
  fig3   strong scaling, measured + Hockney-modeled         [paper Figs 3/5/6]
  fig4   running-time breakdown                             [paper Figs 4/7/8]
  table4 block-size ablation                                [paper Table 4]
  fig5   slab-free vs materialized round (HBM bytes/time)   [EXPERIMENTS §Perf]
  fig6   predict throughput: exact vs low-rank representation,
         batched slab-free vs legacy dense                  [DESIGN §9]
  fig7   sweep throughput: vmapped fleet vs sequential fits,
         warm-started path iteration counts                 [DESIGN §10]
  fig8   guarded-solve price: overhead at the autotuned
         recompute cadence, NaN recovery, resume-after-kill [DESIGN §12]
  fig9   serving SLO: continuous-batching p50/p99 + throughput
         vs the perf-model prediction, overload shedding,
         mid-stream refit correctness                       [DESIGN §13]
  fig10  out-of-core streamed KMV vs resident: modeled overlap
         pipeline + measured parity/ratio gates              [DESIGN §14]
  roofline  assigned-arch roofline table from the dry-run   [EXPERIMENTS §Roofline]

``--fast`` shrinks datasets/iterations (used by CI / test_system).
"""
import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. fig1,table4")
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (fig1_dcd_convergence, fig2_bdcd_convergence,
                            fig3_scaling, fig4_breakdown, fig5_slabfree,
                            fig6_predict, fig7_sweep, fig8_resilience,
                            fig9_serve, fig10_streaming,
                            roofline, table4_blocksize)

    def paper_dist_subprocess(fast=False):
        # needs its own process: it forces a 16-device host platform.
        # It is a CPU model, and this parent has imported JAX and so
        # holds the accelerator: the child runs on the CPU explicitly.
        import os
        import pathlib
        import subprocess
        root = pathlib.Path(__file__).resolve().parents[1]
        env = dict(os.environ)
        env["PYTHONPATH"] = f"{root / 'src'}:{root}"
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        out = subprocess.run(
            [sys.executable, "-m", "benchmarks.paper_dist"]
            + (["--fast"] if fast else []),
            env=env, cwd=str(root), capture_output=True, text=True,
            timeout=1800)
        print("# paper_dist: 16 virtual CPU devices (a CPU model, not a "
              "chip measurement)")
        print(out.stdout, end="")
        if out.returncode != 0:
            raise RuntimeError(out.stderr[-2000:])

    suites = {
        "fig1": fig1_dcd_convergence.run,
        "fig2": fig2_bdcd_convergence.run,
        "fig3": fig3_scaling.run,
        "fig4": fig4_breakdown.run,
        "table4": table4_blocksize.run,
        "fig5": fig5_slabfree.run,
        "fig6": fig6_predict.run,
        "fig7": fig7_sweep.run,
        "fig8": fig8_resilience.run,
        "fig9": fig9_serve.run,
        "fig10": fig10_streaming.run,
        "paper_dist": paper_dist_subprocess,
        "roofline": roofline.run,
    }
    only = set(args.only.split(",")) if args.only else set(suites)
    failed = []
    for name, fn in suites.items():
        if name not in only:
            continue
        print(f"==== {name} ====", flush=True)
        try:
            fn(fast=args.fast)
        except Exception as e:  # pragma: no cover
            failed.append(name)
            print(f"{name},FAILED,{type(e).__name__}: {e}", flush=True)
    if failed:
        sys.exit(f"benchmark suites failed: {failed}")


if __name__ == '__main__':
    main()
