"""Subprocess worker for distributed-solver tests: forces 8 host devices
(must happen before jax import, and must NOT leak into the main pytest
process) and checks distributed == serial."""
import os
import sys

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from repro.api import KernelRidge, KernelSVM, SolverOptions  # noqa: E402
from repro.core import (KernelConfig, KRRConfig, SVMConfig, bdcd_krr,
                        block_schedule, coordinate_schedule, dcd_ksvm,
                        sstep_bdcd_krr)                       # noqa: E402
from repro.core.distributed import (dist_bdcd_krr, dist_dcd_ksvm,
                                    dist_sstep_bdcd_krr,
                                    dist_sstep_bdcd_krr_2d,
                                    dist_sstep_dcd_ksvm,
                                    dist_sstep_dcd_ksvm_2d)   # noqa: E402
from repro.data.synthetic import (classification_dataset,
                                  regression_dataset)         # noqa: E402


def main():
    assert jax.device_count() == 8, jax.devices()
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    failures = []

    # ---- K-SVM: serial DCD vs distributed s-step DCD (1D layout) ----
    # the fused-psum AllreduceGramOperator must match the serial solver.
    A, y = classification_dataset(jax.random.key(0), m=64, n=32)
    cfg = SVMConfig(C=1.0, loss="l1", kernel=KernelConfig("rbf"))
    sched = coordinate_schedule(jax.random.key(1), 32, 64)
    a0 = jnp.zeros(64)
    ref, _ = dcd_ksvm(A, y, a0, sched, cfg)
    for s in (1, 4, 16):
        got = dist_sstep_dcd_ksvm(mesh, A, y, a0, sched, cfg, s=s)
        err = float(jnp.max(jnp.abs(got - ref)))
        print(f"dcd s={s} maxdiff={err:.3e}")
        if err > 5e-5:
            failures.append(f"dcd s={s}")
    got = dist_dcd_ksvm(mesh, A, y, a0, sched, cfg)
    if float(jnp.max(jnp.abs(got - ref))) > 5e-5:
        failures.append("dcd classical")

    # 2D DCD (samples x features) vs serial classical, incl. ragged H
    for H2 in (32, 27):
        sched2 = coordinate_schedule(jax.random.key(1), H2, 64)
        ref2, _ = dcd_ksvm(A, y, a0, sched2, cfg)
        got2 = dist_sstep_dcd_ksvm_2d(mesh, A, y, a0, sched2, cfg, s=8)
        err2 = float(jnp.max(jnp.abs(got2 - ref2)))
        print(f"dcd-2d H={H2} s=8 maxdiff={err2:.3e}")
        if err2 > 5e-5:
            failures.append(f"dcd2d H={H2}")

    # ---- repro.api facade on the REAL 8-device mesh ----
    # every (method, layout), with an explicit mesh and a ragged budget
    for method in ("classical", "sstep"):
        for layout in ("1d", "2d"):
            opts = SolverOptions(method=method, s=8, layout=layout,
                                 mesh=mesh, max_iters=27)
            clf = KernelSVM(C=1.0, loss="l1", kernel=KernelConfig("rbf"),
                            options=opts)
            res = clf.fit(A, y)
            reff, _ = dcd_ksvm(A, y, a0, res.schedule, clf.cfg)
            err = float(jnp.max(jnp.abs(res.alpha - reff)))
            print(f"api ksvm {method}/{layout} maxdiff={err:.3e}")
            if err > 5e-5:
                failures.append(f"api ksvm {method}/{layout}")

    # ---- K-RR: serial BDCD vs distributed (1D + 2D layouts) ----
    A, y = regression_dataset(jax.random.key(2), m=64, n=32)
    kcfg = KRRConfig(lam=0.7, kernel=KernelConfig("polynomial", degree=2,
                                                  coef0=1.0))
    bsched = block_schedule(jax.random.key(3), 16, 64, 4)
    ref, _ = bdcd_krr(A, y, a0, bsched, kcfg)
    for s in (1, 4):
        got = dist_sstep_bdcd_krr(mesh, A, y, a0, bsched, kcfg, s=s)
        err = float(jnp.max(jnp.abs(got - ref)))
        print(f"bdcd-1d s={s} maxdiff={err:.3e}")
        if err > 5e-5:
            failures.append(f"bdcd1d s={s}")
        got2 = dist_sstep_bdcd_krr_2d(mesh, A, y, a0, bsched, kcfg, s=s)
        err2 = float(jnp.max(jnp.abs(got2 - ref)))
        print(f"bdcd-2d s={s} maxdiff={err2:.3e}")
        if err2 > 5e-5:
            failures.append(f"bdcd2d s={s}")
    got = dist_bdcd_krr(mesh, A, y, a0, bsched, kcfg)
    if float(jnp.max(jnp.abs(got - ref))) > 5e-5:
        failures.append("bdcd classical")

    # facade K-RR on the real mesh: tolerance-stopped 1d + 2d runs
    for layout in ("1d", "2d"):
        opts = SolverOptions(method="sstep", s=4, b=4, layout=layout,
                             mesh=mesh, tol=5e-2, check_every=2,
                             max_iters=400)
        res = KernelRidge(lam=1.0, kernel=KernelConfig("rbf"),
                          options=opts).fit(A, y)
        print(f"api krr {layout} tol-stop: converged={res.converged} "
              f"iters={res.iters_run} metric={res.metric_history()[-1]:.3e}")
        if not (res.converged and res.iters_run < 400):
            failures.append(f"api krr {layout} tol")

    # ---- linear kernel: the fully-contracted (no m x sb psum) path ----
    kcfg = KRRConfig(lam=0.7, kernel=KernelConfig("linear"))
    ref, _ = bdcd_krr(A, y, a0, bsched, kcfg)
    got = dist_sstep_bdcd_krr(mesh, A, y, a0, bsched, kcfg, s=4)
    err = float(jnp.max(jnp.abs(got - ref)))
    print(f"bdcd-1d linear slab-free maxdiff={err:.3e}")
    if err > 5e-5:
        failures.append("bdcd1d linear")

    # ---- custom per-rank operator through the op_factory seam ----
    # (DESIGN.md §9): injecting AllreduceGramOperator explicitly must
    # reproduce the default path bit-for-bit on both solver families.
    from repro.core.distributed import AllreduceGramOperator

    def custom_factory(A_loc, kcfg_):
        rs = None
        if kcfg_.name == "rbf":
            rs = jax.lax.psum(jnp.sum(A_loc * A_loc, axis=1), "model")
        return AllreduceGramOperator("model", A_loc, kcfg_, rs)

    kcfg = KRRConfig(lam=1.0, kernel=KernelConfig("rbf", sigma=0.5))
    ref, _ = sstep_bdcd_krr(A, y, a0, bsched, kcfg, s=4)
    got = dist_sstep_bdcd_krr(mesh, A, y, a0, bsched, kcfg, s=4,
                              op_factory=custom_factory)
    err = float(jnp.max(jnp.abs(got - ref)))
    print(f"bdcd-1d custom op_factory maxdiff={err:.3e}")
    if err > 5e-5:
        failures.append("bdcd1d op_factory")
    Ac, yc = classification_dataset(jax.random.key(0), m=64, n=32)
    scfg = SVMConfig(C=1.0, loss="l1", kernel=KernelConfig("rbf"))
    csched = coordinate_schedule(jax.random.key(1), 32, 64)
    ref, _ = dcd_ksvm(Ac, yc, a0, csched, scfg)
    got = dist_sstep_dcd_ksvm(mesh, Ac, yc, a0, csched, scfg, s=4,
                              op_factory=custom_factory)
    err = float(jnp.max(jnp.abs(got - ref)))
    print(f"dcd-1d custom op_factory maxdiff={err:.3e}")
    if err > 5e-5:
        failures.append("dcd1d op_factory")

    # ---- RBF kernel through the 2D path too ----
    kcfg = KRRConfig(lam=1.0, kernel=KernelConfig("rbf", sigma=0.5))
    ref, _ = sstep_bdcd_krr(A, y, a0, bsched, kcfg, s=4)
    got = dist_sstep_bdcd_krr_2d(mesh, A, y, a0, bsched, kcfg, s=4)
    err = float(jnp.max(jnp.abs(got - ref)))
    print(f"bdcd-2d rbf maxdiff={err:.3e}")
    if err > 5e-5:
        failures.append("bdcd2d rbf")

    # ---- low-rank representation on the REAL mesh (DESIGN.md §9) ----
    # same seed -> same landmarks/Phi, so every layout must land on the
    # serial Nystrom iterates; the 1d layout shards Phi's l columns.
    ny = dict(method="sstep", s=4, b=4, max_iters=16, seed=7,
              approx="nystrom", landmarks=16)
    ref_ny = KernelRidge(lam=1.0, kernel=KernelConfig("rbf", sigma=0.5),
                         options=SolverOptions(layout="serial", **ny)
                         ).fit(A, y).alpha
    for layout in ("1d", "2d"):
        res = KernelRidge(lam=1.0, kernel=KernelConfig("rbf", sigma=0.5),
                          options=SolverOptions(layout=layout, mesh=mesh,
                                                **ny)).fit(A, y)
        err = float(jnp.max(jnp.abs(res.alpha - ref_ny)))
        print(f"api krr nystrom {layout} maxdiff={err:.3e}")
        if err > 5e-5:
            failures.append(f"api krr nystrom {layout}")

    # ---- defer_s train step EXECUTES and matches plain training ----
    import dataclasses
    from repro.configs import get_config
    from repro.models import init_params
    from repro.optim import AdamWConfig, adamw_init
    from repro.train.train_step import (TrainConfig, make_defer_train_step,
                                        make_train_step)
    from repro.data.tokens import TokenPipeline

    cfg = dataclasses.replace(get_config("qwen3_1p7b", reduced=True),
                              remat="none")
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    from repro.models.sharding import MeshRules
    rules = MeshRules(mesh)
    acfg = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10)
    pipe = TokenPipeline(vocab_size=cfg.vocab_size, seq_len=16,
                         global_batch=16, seed=3)

    p1 = init_params(jax.random.key(5), cfg)
    o1 = adamw_init(p1)
    plain = make_train_step(cfg, acfg, TrainConfig(microbatches=4))
    p2 = init_params(jax.random.key(5), cfg)   # fresh buffers: the steps
    o2 = adamw_init(p2)                        # donate their inputs
    defer = make_defer_train_step(cfg, acfg,
                                  TrainConfig(microbatches=4, defer_s=4),
                                  rules)
    for step in range(2):
        batch = pipe.batch(step)
        p1, o1, m1 = plain(p1, o1, batch)
        p2, o2, m2 = defer(p2, o2, batch)
        dl = abs(float(m1["loss"]) - float(m2["loss"]))
        print(f"defer step {step}: plain={float(m1['loss']):.5f} "
              f"defer={float(m2['loss']):.5f}")
        if dl > 5e-3:
            failures.append(f"defer loss mismatch {dl}")
    dev = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    print(f"defer param maxdiff after 2 steps: {dev:.2e}")
    if dev > 5e-3:
        failures.append(f"defer param dev {dev}")

    if failures:
        print("FAILURES:", failures)
        sys.exit(1)
    print("ALL-OK")


if __name__ == "__main__":
    main()
