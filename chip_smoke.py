"""Chip smoke test: the fit-and-serve main path once, at real size, on a TPU.

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --chips 4     # 1d/2d layouts on four chips vs serial

Data is made on the device from ``--seed``: a dense training set of
m=262144 rows and n=256 f32 features (256 MiB; K-SVM adds its 256 MiB
``diag(y) A`` copy), rbf kernel.  One process holds the chip for the
whole run; nothing is started beside it.

One-chip phases, each on its own line with what it checked and its wall
time, first with compilation included (``cold_s``) and then warm:

  device      the default device is a TPU (else exit 2, no result line)
  krr         ``KernelRidge.fit``, s-step (s=16, b=8) and classical, agree;
              the relative residual recomputed by a plain f32 reference
              matches ``krr_rel_residual`` and is below 1; the ``tol>0``
              path stops on its tolerance
  ksvm        ``KernelSVM.fit``, s-step (s=16) and classical, agree;
              ``ksvm_duality_gap`` timed on its own line
  closed_form m=4096 K-RR against ``krr_closed_form``
  pallas      ``kmv_pallas`` and ``kmv_stream_pallas`` compiled for the chip
              (never interpret mode), against ``kmv_slab_free``
  serve       both models in one ``ModelRegistry`` group behind a warmed
              ``ServingEngine``: results against a blocked dense reference,
              and no compile after warmup

With ``--chips 4`` the script fits K-RR and K-SVM at the same size in the
1d and 2d layouts over four devices and compares each with the serial fit
on ``devices[0]``, and runs no other phase.

Every comparison is normwise, in float64 on the host:
``rel(a, b) = ||a - b|| / ||b||``.  On a TPU an f32 matrix product at
default precision is one bfloat16 pass (relative error up to 2^-8 per
product, accumulated in f32), so two mathematically equal computations
that round differently differ at that level; each bound below states how
it follows from that.  Any failed check raises and the script exits
non-zero.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

M, N = 262144, 256            # the deployment: 2^18 rows x 256 features
SIGMA, LAM, C = 1.0, 1.0, 1.0
S, B = 16, 8                  # s-step depth, K-RR block size
H = 4096                      # inner-iteration budget of every fixed fit
M_SMALL = 4096                # closed-form check size
R = 128                       # sampled rows of the kernel checks
QUERY_ROWS = (64, 512, 128, 256, 96, 200)

# Bounds (module docstring: normwise, float64 on the host).
# s-step vs classical, and 1d/2d vs serial: the same iterates in exact
# arithmetic; each coordinate update reads K-products that carry
# bf16-pass error (2^-8 relative), so the iterates may differ at a few
# times that, scaled by ||alpha||.
AGREE_KRR = 1e-3
AGREE_KSVM = 2e-2
# the script's reference residual (HIGHEST precision) vs the library's
# (default precision): both are dominated by the exact m*alpha term, so
# they agree far inside the bf16 level.
RESIDUAL_AGREE = 1e-3
KRR_TOL = 0.99                # tol>0 path: reached after ~2% of coordinates
CLOSED_FORM = 1e-3            # fit (to its f32 floor) vs closed form
KMV_AGREE = 2e-2              # Pallas KMV vs the XLA scan: ~5x 2^-8
SERVE_AGREE = 2e-2            # served values vs the HIGHEST reference


class SmokeFailure(AssertionError):
    pass


def check(name: str, ok: bool, detail: str) -> None:
    if not ok:
        raise SmokeFailure(f"{name}: {detail}")


def line(phase: str, cold=None, warm=None, **vals) -> None:
    parts = [f"[{phase}]"] + [f"{k}={v}" for k, v in vals.items()]
    if cold is not None:
        parts.append(f"cold_s={cold:.4f}")
    if warm is not None:
        parts.append(f"warm_s={warm:.4f}")
    print(" ".join(parts), flush=True)


def timed(fn):
    """Run ``fn`` twice to completion: (result, cold s, warm s)."""
    import jax

    def once():
        t0 = time.perf_counter()
        out = fn()
        parts = out if isinstance(out, tuple) else (out,)
        jax.block_until_ready([getattr(x, "alpha", x) for x in parts])
        return out, time.perf_counter() - t0

    _, cold = once()
    out, warm = once()
    return out, cold, warm


def rel(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# --------------------------------------------------------------------------
# plain f32 references, independent of the library's KMV
# --------------------------------------------------------------------------

def _ref_apply_impl(Q, A, W, sigma: float, rows: int):
    """``K(Q, A) @ W`` for the rbf kernel, ``rows`` rows of Q at a time,
    every product at HIGHEST (true f32) precision."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    an = jnp.sum(A * A, axis=1)

    def block(Qb):
        d = jnp.dot(Qb, A.T, precision=hi)
        sq = jnp.sum(Qb * Qb, axis=1)[:, None] + an[None, :] - 2.0 * d
        return jnp.dot(jnp.exp(-sigma * jnp.maximum(sq, 0.0)), W,
                       precision=hi)

    q = Q.shape[0]
    pad = (-q) % rows
    Qp = jnp.pad(Q, ((0, pad), (0, 0))).reshape(-1, rows, Q.shape[1])
    return jax.lax.map(block, Qp).reshape(-1, W.shape[1])[:q]


def ref_apply(Q, A, W, rows: int = 512):
    import jax
    f = jax.jit(partial(_ref_apply_impl, sigma=SIGMA, rows=rows))
    return f(Q, A, W)


def ref_rel_residual(A, y, alpha, lam: float) -> float:
    """``||y - (K alpha / lam + m alpha)|| / ||y||`` (the repo's K-RR
    system, ``objectives.krr_rel_residual``), K @ alpha by row blocks."""
    import numpy as np
    Ka = np.asarray(ref_apply(A, A, alpha[:, None]), np.float64)[:, 0]
    a = np.asarray(alpha, np.float64)
    yv = np.asarray(y, np.float64)
    r = yv - (Ka / lam + A.shape[0] * a)
    return float(np.linalg.norm(r) / np.linalg.norm(yv))


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def phase_device(chips: int):
    import jax

    from repro.compile_cache import use_compile_cache
    if os.environ.get("REPRO_SANITIZE", "") == "1":
        print("chip_smoke: REPRO_SANITIZE=1 forces Pallas interpret mode "
              "on the TPU; unset it to run the chip path", file=sys.stderr)
        sys.exit(2)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX default device is "
              f"{devs[0].platform!r}); this script runs on the chip only",
              file=sys.stderr)
        sys.exit(2)
    if len(devs) < chips:
        print(f"chip_smoke: --chips {chips} needs {chips} devices, JAX "
              f"sees {len(devs)}", file=sys.stderr)
        sys.exit(2)
    cache = use_compile_cache()
    line("device", platform=devs[0].platform,
         kind=repr(devs[0].device_kind), count=len(devs),
         jax=jax.__version__, compile_cache=cache,
         hbm_limit=(devs[0].memory_stats() or {}).get("bytes_limit"))
    return devs


def make_data(seed: int):
    import jax

    from repro.data.synthetic import classification_dataset
    (A, y), cold, warm = timed(
        lambda: classification_dataset(jax.random.key(seed), M, N))
    line("data", m=M, n=N, dtype=A.dtype, bytes=A.nbytes, cold=cold,
         warm=warm)
    return A, y


def phase_krr(A, y, seed: int):
    import dataclasses

    from repro.api import KernelRidge, SolverOptions
    from repro.core import krr_rel_residual

    def fit(**kw):
        est = KernelRidge(lam=LAM, kernel="rbf",
                          options=SolverOptions(b=B, max_iters=H,
                                                seed=seed, **kw))
        return est, est.fit(A, y)

    (est_s, res_s), c_s, w_s = timed(lambda: fit(method="sstep", s=S))
    line("krr_sstep", s=S, b=B, iters=res_s.iters_run, cold=c_s, warm=w_s)
    (est_c, res_c), c_c, w_c = timed(lambda: fit(method="classical"))
    line("krr_classical", b=B, iters=res_c.iters_run, cold=c_c, warm=w_c)
    agree = rel(res_s.alpha, res_c.alpha)
    check("krr sstep vs classical", agree <= AGREE_KRR,
          f"rel {agree:.3e} > {AGREE_KRR}")

    r_ref = ref_rel_residual(A, y, res_s.alpha, LAM)
    r_lib, c_r, w_r = timed(
        lambda: krr_rel_residual(A, y, res_s.alpha, est_s.cfg))
    r_lib = float(r_lib)
    gap = abs(r_ref - r_lib) / r_ref
    check("krr residual reference", gap <= RESIDUAL_AGREE,
          f"ref {r_ref:.6e} vs krr_rel_residual {r_lib:.6e}")
    check("krr residual fell", r_ref < 1.0, f"residual {r_ref:.6e}")
    line("krr_check", sstep_vs_classical=f"{agree:.3e}",
         bound=AGREE_KRR, residual_ref=f"{r_ref:.6e}",
         residual_lib=f"{r_lib:.6e}", residual_rel_diff=f"{gap:.3e}",
         residual_bound=RESIDUAL_AGREE, cold=c_r, warm=w_r)

    opts_t = dataclasses.replace(est_s.options, tol=KRR_TOL, check_every=8)
    res_t, c_t, w_t = timed(
        lambda: KernelRidge(lam=LAM, kernel="rbf",
                            options=opts_t).fit(A, y))
    last = float(res_t.history[-1])
    check("krr tol path", res_t.converged and last <= KRR_TOL
          and res_t.iters_run < H,
          f"converged={res_t.converged} last={last} "
          f"iters={res_t.iters_run}")
    line("krr_tol", tol=KRR_TOL, converged=res_t.converged,
         iters=res_t.iters_run, checks=len(res_t.history),
         residual=f"{last:.6e}", cold=c_t, warm=w_t)
    return est_s


def phase_ksvm(A, y, seed: int):
    from repro.api import KernelSVM, SolverOptions
    from repro.core import ksvm_duality_gap

    def fit(**kw):
        est = KernelSVM(C=C, kernel="rbf",
                        options=SolverOptions(max_iters=H, seed=seed, **kw))
        return est, est.fit(A, y)

    (est_s, res_s), c_s, w_s = timed(lambda: fit(method="sstep", s=S))
    line("ksvm_sstep", s=S, iters=res_s.iters_run, cold=c_s, warm=w_s)
    (est_c, res_c), c_c, w_c = timed(lambda: fit(method="classical"))
    line("ksvm_classical", iters=res_c.iters_run, cold=c_c, warm=w_c)
    agree = rel(res_s.alpha, res_c.alpha)
    check("ksvm sstep vs classical", agree <= AGREE_KSVM,
          f"rel {agree:.3e} > {AGREE_KSVM}")
    line("ksvm_check", sstep_vs_classical=f"{agree:.3e}", bound=AGREE_KSVM)

    gap, c_g, w_g = timed(
        lambda: ksvm_duality_gap(A, y, res_s.alpha, est_s.cfg))
    gap = float(gap)
    check("ksvm duality gap finite", gap == gap and abs(gap) < float("inf"),
          f"gap {gap}")
    line("ksvm_gap", gap=f"{gap:.6e}", cold=c_g, warm=w_g)
    return est_s


def phase_closed_form(A, y, seed: int):
    from repro.api import KernelRidge, SolverOptions
    from repro.core import krr_closed_form

    As, ys = A[:M_SMALL], y[:M_SMALL]
    est = KernelRidge(lam=LAM, kernel="rbf",
                      options=SolverOptions(s=S, b=B, max_iters=8192,
                                            seed=seed))
    res, c_f, w_f = timed(lambda: est.fit(As, ys))
    star = krr_closed_form(As, ys, est.cfg)
    err = rel(res.alpha, star)
    check("closed form", err <= CLOSED_FORM,
          f"rel err {err:.3e} > {CLOSED_FORM}")
    line("closed_form", m=M_SMALL, iters=res.iters_run,
         rel_err=f"{err:.3e}", bound=CLOSED_FORM, cold=c_f, warm=w_f)


def phase_pallas(A, seed: int):
    import jax
    import jax.numpy as jnp

    from repro.core.kernels import KernelConfig, kmv_slab_free
    from repro.core.perf_model import choose_chunk_rows
    from repro.kernels import ops

    check("pallas compiled", ops._interpret() is False,
          "ops._interpret() is True: kernels would run interpreted")
    X = jax.random.normal(jax.random.key(seed + 1), (M,), jnp.float32)
    idx = jnp.arange(R)
    B_ = A[:R]
    auto = choose_chunk_rows(M, N, R, "rbf")
    for cfg in (KernelConfig("linear"),
                KernelConfig("polynomial", degree=3, coef0=1.0),
                KernelConfig("rbf", sigma=SIGMA)):
        want = kmv_slab_free(A, B_, X, cfg)
        ops_ = [("kmv_pallas", ops.make_solver_op_factory()(A, cfg))]
        for cr in sorted({auto, 2048}):
            ops_.append((f"kmv_stream_pallas[chunk={cr}]",
                         ops.make_streaming_op_factory(cr)(A, cfg)))
        for name, op in ops_:
            got, cold, warm = timed(lambda op=op: op.matvec(idx, X))
            err = rel(got, want)
            check(f"{name} {cfg.name}", err <= KMV_AGREE,
                  f"rel {err:.3e} > {KMV_AGREE}")
            line("pallas", kernel=name, fn=cfg.name, m=M, n=N, r=R,
                 rel_vs_xla=f"{err:.3e}", bound=KMV_AGREE, cold=cold,
                 warm=warm)


def phase_serve(A, y, krr, svm, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.predict import serve_cache_size
    from repro.serve import ModelRegistry, ServingEngine

    t0 = time.perf_counter()
    reg = ModelRegistry(predict_batch=max(QUERY_ROWS))
    reg.register("krr", krr)
    reg.register("svm", svm)
    check("one group", reg.n_groups == 1,
          f"{reg.n_groups} operator groups for one training set")
    eng = ServingEngine(reg, slots=max(QUERY_ROWS))
    buckets = eng.warmup()
    t_warm = time.perf_counter() - t0
    c0 = serve_cache_size()

    Q = np.asarray(jax.random.normal(
        jax.random.key(seed + 2), (sum(QUERY_ROWS), N), jnp.float32)
        / np.sqrt(N)).astype(np.float32)
    t1 = time.perf_counter()
    tickets, lo = [], 0
    for k, rows in enumerate(QUERY_ROWS):
        tickets.append(eng.submit("krr" if k % 2 == 0 else "svm",
                                  Q[lo:lo + rows]))
        lo += rows
    steps = 0
    while eng.pending:
        eng.step()
        steps += 1
    t_serve = time.perf_counter() - t1
    check("no compile after warmup", serve_cache_size() == c0,
          f"serve cache grew {c0} -> {serve_cache_size()}")

    W = jnp.stack([krr.alpha_ / LAM, svm.alpha_ * svm.y_], axis=1)
    ref = np.asarray(ref_apply(jnp.asarray(Q), A, W), np.float64)
    worst, lo = 0.0, 0
    for k, t in enumerate(tickets):
        check("ticket done", t.status == "done", f"ticket {t.id} {t.status}")
        err = rel(t.result, ref[lo:lo + t.rows, k % 2])
        worst = max(worst, err)
        lo += t.rows
    check("served values", worst <= SERVE_AGREE,
          f"worst rel {worst:.3e} > {SERVE_AGREE}")
    line("serve", models=2, groups=reg.n_groups, buckets=buckets,
         requests=len(tickets), rows=sum(QUERY_ROWS), steps=steps,
         worst_rel=f"{worst:.3e}", bound=SERVE_AGREE,
         cache_growth=serve_cache_size() - c0,
         register_warmup_s=f"{t_warm:.4f}", serve_s=f"{t_serve:.4f}")


def phase_four_chips(A, y, seed: int):
    import jax

    from repro.api import KernelRidge, KernelSVM, SolverOptions

    for problem, bound in (("krr", AGREE_KRR), ("ksvm", AGREE_KSVM)):
        def fit(layout, problem=problem):
            opts = SolverOptions(method="sstep", s=S,
                                 b=B if problem == "krr" else 1,
                                 layout=layout, max_iters=H, seed=seed)
            est = (KernelRidge(lam=LAM, kernel="rbf", options=opts)
                   if problem == "krr"
                   else KernelSVM(C=C, kernel="rbf", options=opts))
            return est.fit(A, y)

        serial, c_s, w_s = timed(lambda: fit("serial"))
        line(f"{problem}_serial", device=str(serial.alpha.devices()),
             cold=c_s, warm=w_s)
        for layout in ("1d", "2d"):
            res, cold, warm = timed(lambda layout=layout: fit(layout))
            agree = rel(res.alpha, serial.alpha)
            check(f"{problem} {layout} vs serial", agree <= bound,
                  f"rel {agree:.3e} > {bound}")
            line(f"{problem}_{layout}", P=res.comm["P"],
                 vs_serial=f"{agree:.3e}", bound=bound, cold=cold,
                 warm=warm)
    line("memory", bytes_in_use=[(d.memory_stats() or {}).get(
        "bytes_in_use") for d in jax.devices()])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    devs = phase_device(args.chips)
    A, y = make_data(args.seed)
    if args.chips == 4:
        phase_four_chips(A, y, args.seed)
    else:
        krr = phase_krr(A, y, args.seed)
        svm = phase_ksvm(A, y, args.seed)
        phase_closed_form(A, y, args.seed)
        phase_pallas(A, args.seed)
        phase_serve(A, y, krr, svm, args.seed)
    line("memory", peak_bytes_in_use=(devs[0].memory_stats() or {}).get(
        "peak_bytes_in_use"))
    line("total", wall_s=f"{time.perf_counter() - t0:.4f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
