"""Figure 6 (beyond paper): prediction throughput through the
representation hierarchy (DESIGN.md §9).

The legacy predict path (``objectives.ksvm_predict`` / ``krr_predict``)
materializes the dense (q x m) test-kernel slab against the full
training set — training is slab-free, serving was not.  This
sweep measures queries/second for:

  * legacy dense predict (the (q x m) ``gram_slab`` oracle),
  * batched slab-free predict (``core/predict.py``, fixed-block jit
    cache) over the EXACT representation,
  * batched predict over the LOW-RANK (Nystrom) representation —
    O(l) per query after the (l,)-word ``Phi^T w`` precompute,

for both estimators, plus the modeled per-query flops
(``perf_model.modeled_predict_cost``) so the measured ratios can be
checked against the model.

Acceptance gate: batched slab-free predictions must match the legacy
dense oracle to <= 1e-5 (exact representation, both estimators).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import KernelRidge, KernelSVM, SolverOptions
from repro.core import KernelConfig, KRRConfig
from repro.core.objectives import krr_predict, ksvm_predict
from repro.core.perf_model import modeled_predict_cost
from repro.data.synthetic import classification_dataset, regression_dataset

from .common import emit, save_json, timeit

LANDMARKS = 128
BATCH = 512


def _throughput(fn, q, iters=3):
    t = timeit(fn, iters=iters)
    return {"t_s": t, "queries_per_s": q / t}


def sweep(fast: bool = False):
    m, n = (768, 32) if fast else (8192, 64)
    q = 512 if fast else 4096
    kern = KernelConfig("rbf", sigma=1.0)
    H = 64 if fast else 256
    rows = []

    # ---- K-RR -----------------------------------------------------------
    A, y = regression_dataset(jax.random.key(0), m, n)
    Q = regression_dataset(jax.random.key(1), q, n)[0]
    base = dict(method="sstep", s=8, b=4, max_iters=H, seed=1)
    reps = {
        "exact": SolverOptions(**base),
        "nystrom": SolverOptions(approx="nystrom", landmarks=LANDMARKS,
                                 **base),
    }
    for rep, opts in reps.items():
        reg = KernelRidge(lam=1.0, kernel=kern, options=opts,
                          predict_batch=BATCH)
        res = reg.fit(A, y)
        batched = _throughput(lambda: reg.predict(Q), q)
        if rep == "exact":
            legacy = _throughput(
                lambda: krr_predict(A, res.alpha, Q, reg.cfg), q)
            np.testing.assert_allclose(
                np.asarray(reg.predict(Q)),
                np.asarray(krr_predict(A, res.alpha, Q, reg.cfg)),
                rtol=1e-5, atol=1e-5)
        else:
            lin = KRRConfig(lam=1.0, kernel=KernelConfig("linear"))
            legacy = _throughput(
                lambda: krr_predict(reg.op_.Phi, res.alpha,
                                    reg.op_.fmap(Q), lin), q)
        model = modeled_predict_cost(
            m, n, q, kern.name,
            approx=opts.approx, landmarks=LANDMARKS)
        rows.append({"estimator": "krr", "representation": rep,
                     "m": m, "n": n, "q": q, "batch": BATCH,
                     "legacy_dense": legacy, "batched_slabfree": batched,
                     "modeled_flops_per_query": model["flops_per_query"]})
        emit(f"fig6/krr/{rep}", batched["t_s"] * 1e6,
             f"batched={batched['queries_per_s']:.0f}q/s;"
             f"legacy={legacy['queries_per_s']:.0f}q/s")

    # ---- K-SVM (decision values; SV-compacted serving) ------------------
    A, y = classification_dataset(jax.random.key(2), m, n)
    Q = classification_dataset(jax.random.key(3), q, n)[0]
    for rep, opts in reps.items():
        clf = KernelSVM(C=1.0, kernel=kern, options=opts,
                        predict_batch=BATCH)
        res = clf.fit(A, y)
        n_sv = int(jnp.sum(res.alpha != 0))
        batched = _throughput(lambda: clf.decision_function(Q), q)
        if rep == "exact":
            legacy = _throughput(
                lambda: ksvm_predict(A, y, res.alpha, Q, clf.cfg), q)
            np.testing.assert_allclose(
                np.asarray(clf.decision_function(Q)),
                np.asarray(ksvm_predict(A, y, res.alpha, Q, clf.cfg)),
                rtol=1e-5, atol=1e-5)
        else:
            legacy = _throughput(
                lambda: clf.op_.fmap(Q) @ (clf.op_.Phi.T
                                           @ (res.alpha * y)), q)
        model = modeled_predict_cost(
            m, n, q, kern.name, approx=opts.approx, landmarks=LANDMARKS,
            sv_fraction=n_sv / m)
        rows.append({"estimator": "ksvm", "representation": rep,
                     "m": m, "n": n, "q": q, "batch": BATCH,
                     "n_sv": n_sv,
                     "legacy_dense": legacy, "batched_slabfree": batched,
                     "modeled_flops_per_query": model["flops_per_query"]})
        emit(f"fig6/ksvm/{rep}", batched["t_s"] * 1e6,
             f"batched={batched['queries_per_s']:.0f}q/s;"
             f"legacy={legacy['queries_per_s']:.0f}q/s;sv={n_sv}/{m}")
    return rows


def run(fast: bool = False):
    rows = sweep(fast)
    print(f"fig6: batched slab-free predict matches the legacy dense "
          f"oracle (<=1e-5) on both estimators; "
          f"{len(rows)} (estimator x representation) configs")
    save_json("fig6_predict.json", rows)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    run(fast=ap.parse_args().fast)
