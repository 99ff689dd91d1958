"""Vmapped multi-problem solver fleets (DESIGN.md §10).

Hyperparameter search is the dominant real workload for kernel methods:
every point of a lambda/C grid is a FULL solve against the SAME data, so
solving them one at a time re-reads and re-epilogues the same kernel
slabs once per grid point.  A *fleet* instead solves F related problems
in ONE jitted computation: the shared round-protocol loop runs over a
batched state pytree (alpha: (F, m)) with the regularization scalar as
a batched cfg leaf (``make_*_round_fn(..., lam=/C=)``), vmapped per
member.

Why this amortizes the dominant cost: the fleet shares ONE
``GramOperator`` (exact or low-rank — operators are registered pytrees,
DESIGN.md §9).  Under ``jax.vmap`` only values that depend on the batch
axis are batched; the operator's leaves and the round's sampled rows do
not, so the slab GEMM and its nonlinear epilogue — the paper's dominant
per-round terms — are computed ONCE per round for the whole fleet, and
only the O(m)-per-member contraction ``U^T alpha_f``, the O((sb)^2)
correction solves, and the state updates scale with F
(``perf_model.fleet_fit_cost`` prices exactly this split; the measured
counterpart is ``benchmarks/fig7_sweep.py``).

Tolerance stopping is per member (``loop.run_rounds_fleet``): each
member checks its own convergence metric, converged members are frozen
in place (their lockstep updates are masked off), and the loop exits
when the whole fleet is done.

Layouts: ``serial`` vmaps the serial round fns; ``1d`` vmaps INSIDE the
``shard_map`` body, so the per-round psum payload batches only where the
member states do — for nonlinear exact kernels the pre-epilogue
``m x sb`` all-reduce stays SHARED across the fleet (same words as a
single solve).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (KRRConfig, NO_TOL, SVMConfig, kmv_slab_free,
                        block_schedule, coordinate_schedule,
                        make_sstep_bdcd_round_fn, make_sstep_dcd_round_fn,
                        pad_rounds, run_rounds_fleet)
from repro.core.objectives import ksvm_gap_from_Qa, krr_rel_residual_value
from repro.core.perf_model import fleet_fit_cost

FLEET_LAYOUTS = ("serial", "1d")


# repro: noqa[CHK-PYTREE] host-side result record assembled AFTER the
#   jitted fleet chunks return; never re-enters a traced function.
@dataclasses.dataclass
class FleetResult:
    """Everything ``solve_fleet`` observed, fleet-wide.

    ``alpha[f]`` is member f's solution for ``values[f]``;
    ``history[:, f]`` its convergence trajectory (``metric_history``);
    ``comm`` the modeled fleet cost (``perf_model.fleet_fit_cost`` —
    includes the modeled ``sequential_time`` of F independent fits and
    the implied ``modeled_speedup``).
    """

    alpha: jnp.ndarray             # (F, m)
    values: np.ndarray             # (F,) the lambda/C grid, input order
    param: str                     # "lam" | "C"
    problem: str                   # "krr" | "ksvm"
    history: Optional[np.ndarray]  # (checks_run, F) or None
    metric: str                    # "rel_residual" | "duality_gap"
    converged: np.ndarray          # (F,) bool
    rounds_run: int
    iters_run: int
    wall_time_s: float
    comm: dict
    options: object                # the (resolved) SolverOptions
    representation: str
    op: object = None              # shared representation operator
                                   # (raw-data; serve fleet predictions
                                   # through it — see cross_validate)

    def metric_history(self, member: Optional[int] = None):
        """Evaluated trajectory: (checks, F), or member f's (checks,)."""
        if self.history is None:
            return None
        return self.history if member is None else self.history[:, member]


def _member_metric(problem, A_s, y, cfg_s):
    """Per-member convergence metric with the regularizer TRACED —
    ``(alpha, value) -> scalar``, vmapped over the fleet.  The formulas
    are the facade's own stopper cores (``objectives.
    krr_rel_residual_value`` / ``ksvm_gap_from_Qa``) — one definition,
    two drivers.  The kernel matvec runs slab-free through
    ``kmv_slab_free`` over the SOLVE representation (A for exact, Phi +
    linear for low-rank — the linear branch IS the factored
    ``ksvm_duality_gap_lowrank`` contraction), so under vmap the kernel
    tiles are built once for all F metrics."""
    kern = cfg_s.kernel

    if problem == "krr":
        return lambda alpha, lam: krr_rel_residual_value(A_s, y, alpha,
                                                         lam, kern)
    loss = cfg_s.loss

    def metric(alpha, C):
        Qa = y * kmv_slab_free(A_s, A_s, y * alpha, kern)
        return ksvm_gap_from_Qa(Qa, alpha, C, loss)
    return metric


def _make_fleet_round_fn(problem, A_s, y, cfg_s, s, op, params):
    """The vmapped lockstep round: per-member round fns built from the
    SAME factories the facade drives, with the regularizer as the
    batched cfg leaf.  ``op`` (shared, unbatched) is closed over — vmap
    keeps every reduction that ignores the batch axis un-replicated.
    Its loop-ready form is built here, outside the vmap and the loop."""
    op = op.for_rounds()
    if problem == "ksvm":
        def member(alpha, p, xs):
            rf = make_sstep_dcd_round_fn(A_s, y, cfg_s, s, op=op, C=p)
            return rf(alpha, xs)
    else:
        def member(alpha, p, xs):
            rf = make_sstep_bdcd_round_fn(A_s, y, cfg_s, s, op=op, lam=p)
            return rf(alpha, xs)
    vround = jax.vmap(member, in_axes=(0, 0, None))
    return lambda state, x: vround(state, params, x)


@partial(jax.jit, static_argnames=("problem", "cfg", "s", "check_every",
                                   "want_metric"))
def _fleet_serial(A_s, y, a0F, params, schedule, tol, op, *, problem,
                  cfg, s, check_every, want_metric):
    round_fn = _make_fleet_round_fn(problem, A_s, y, cfg, s, op, params)
    xs = pad_rounds(schedule, s)
    metric_fn = None
    if want_metric:
        mm = _member_metric(problem, A_s, y, cfg)
        metric_fn = lambda st: jax.vmap(mm)(st, params)
    return run_rounds_fleet(round_fn, a0F, xs, tol=tol,
                            check_every=check_every, metric_fn=metric_fn)


@partial(jax.jit, static_argnames=("problem", "cfg", "s", "mesh",
                                   "axis_name"))
def _fleet_1d_chunk(A_s, y, a0F, params, schedule, *, problem, cfg, s,
                    mesh, axis_name="model"):
    """One jitted chunk of 1d-layout fleet rounds: the vmap sits INSIDE
    the shard_map body, so per-rank operators are built once per chunk
    and shared psums stay unbatched across the fleet."""
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map
    from repro.core.distributed import (AllreduceGramOperator,
                                        _psummed_row_sqnorms)

    @partial(shard_map, mesh=mesh,
             in_specs=(P(None, axis_name), P(), P(), P(), P()),
             out_specs=P(), check_vma=False)
    def run(A_loc, y_r, a0F_r, params_r, sched_r):
        data_loc = (y_r[:, None] * A_loc if problem == "ksvm" else A_loc)
        rs = _psummed_row_sqnorms(data_loc, cfg.kernel, axis_name)
        op = AllreduceGramOperator(axis_name, data_loc, cfg.kernel, rs)
        round_fn = _make_fleet_round_fn(problem, A_loc, y_r, cfg, s, op,
                                        params_r)
        xs = pad_rounds(sched_r, s)
        return run_rounds_fleet(round_fn, a0F_r, xs).state

    return run(A_s, y, a0F, params, schedule)


def solve_fleet(A, y, *, lams=None, Cs=None, kernel=None, loss: str = "l1",
                options=None, warm_start=None) -> FleetResult:
    """Solve F independent problems — a lambda grid (K-RR, ``lams``) or a
    C grid (K-SVM, ``Cs``) on shared data — in ONE vmapped computation
    over one shared representation operator (module docstring).

    ``options`` is the facade's ``SolverOptions`` (auto knobs resolve
    through the autotuner first); fleets are slab-free by construction
    and support the ``serial`` and ``1d`` layouts.  ``warm_start`` seeds
    the whole fleet — (F, m) per-member, or (m,) broadcast (e.g. the
    solution at a neighbouring grid point).
    """
    from repro.api import (SolverOptions, _as_kernel,
                           _build_representation, _resolve_mesh,
                           _solve_cfg)

    if (lams is None) == (Cs is None):
        raise ValueError("pass exactly one of lams= (K-RR fleet) or "
                         "Cs= (K-SVM fleet)")
    problem = "krr" if Cs is None else "ksvm"
    values = np.asarray(lams if Cs is None else Cs, dtype=np.float64)
    if values.ndim != 1 or values.size < 1:
        raise ValueError(f"the {'lams' if Cs is None else 'Cs'} grid must "
                         f"be a non-empty 1-D sequence, got shape "
                         f"{values.shape}")
    if np.any(values <= 0.0):
        raise ValueError("regularization values must be positive")
    opts = options or SolverOptions()

    m, n = A.shape
    F = values.size
    if problem == "krr":
        cfg = KRRConfig(lam=1.0, kernel=_as_kernel(kernel))
    else:
        cfg = SVMConfig(C=1.0, loss=loss, kernel=_as_kernel(kernel))

    if opts.needs_autotune:
        from .autotune import resolve_options
        plan = resolve_options(m, n, cfg, opts, problem=problem, A=A, y=y,
                               layouts=FLEET_LAYOUTS)
        opts = plan.options
    if opts.layout not in FLEET_LAYOUTS:
        raise ValueError(f"fleet layout must be one of {FLEET_LAYOUTS}, "
                         f"got {opts.layout!r} (2d fleets: shard the "
                         f"members, not the samples — open item)")

    H = opts.max_iters
    s = opts.s_eff
    b = opts.b if problem == "krr" else 1
    key = jax.random.key(opts.seed)
    if problem == "ksvm":
        schedule = coordinate_schedule(key, H, m)
        metric_name = "duality_gap"
    else:
        schedule = block_schedule(key, H, m, b)
        metric_name = "rel_residual"

    t0 = time.perf_counter()
    rep_op, A_s = _build_representation(A, cfg, opts)
    cfg_s = _solve_cfg(cfg, opts)
    train_op = rep_op.scale_rows(y) if problem == "ksvm" else rep_op
    params = jnp.asarray(values, A.dtype)
    if warm_start is None:
        a0F = jnp.zeros((F, m), A.dtype)
    else:
        a0F = jnp.broadcast_to(jnp.asarray(warm_start, A.dtype),
                               (F, m)).copy()

    want_metric = opts.tol > 0.0 or opts.record
    tol = opts.tol if opts.tol > 0.0 else NO_TOL
    history = None
    converged = np.zeros(F, bool)

    if opts.layout == "serial":
        P_count = 1
        res = _fleet_serial(A_s, y, a0F, params, schedule, tol, train_op,
                            problem=problem, cfg=cfg_s, s=s,
                            check_every=opts.check_every,
                            want_metric=want_metric)
        alpha = res.state
        rounds_run = int(res.rounds_run)
        if want_metric:
            converged = np.asarray(res.converged)
            history = np.asarray(res.metric_history())
    else:
        mesh = _resolve_mesh(opts)
        P_count = mesh.shape["model"]
        dist_kw = dict(problem=problem, cfg=cfg_s, s=s, mesh=mesh)
        if not want_metric:
            alpha = _fleet_1d_chunk(A_s, y, a0F, params, schedule,
                                    **dist_kw)
            rounds_run = -(-H // s)
        else:
            # chunked per-member stopping, mirroring the facade's 1d
            # tolerance path: whole multiples of s per chunk keep the
            # round decomposition identical; converged members are
            # frozen on the host between chunks
            mm = jax.jit(jax.vmap(_member_metric(problem, A_s, y, cfg_s)))
            chunk = opts.check_every * s
            done = np.zeros(F, bool)
            pos, rounds_run, hist = 0, 0, []
            alpha = a0F
            while pos < H:
                sched_c = schedule[pos:pos + chunk]
                new = _fleet_1d_chunk(A_s, y, alpha, params, sched_c,
                                      **dist_kw)
                alpha = jnp.where(jnp.asarray(done)[:, None], alpha, new)
                pos += sched_c.shape[0]
                rounds_run += -(-sched_c.shape[0] // s)
                vals = np.asarray(mm(alpha, params))
                hist.append(vals)
                if opts.tol > 0.0:
                    done |= vals <= opts.tol
                    if done.all():
                        break
            converged = done
            history = np.asarray(hist)
    jax.block_until_ready(alpha)
    wall = time.perf_counter() - t0

    iters_run = min(rounds_run * s, H)
    l = A_s.shape[1] if opts.approx else 0
    comm = fleet_fit_cost(m, n, cfg.kernel.name, F, b=b, s=s,
                          iters=iters_run, P=P_count, approx=opts.approx,
                          landmarks=l)
    rep_name = f"nystrom(l={l})" if opts.approx else "exact"
    return FleetResult(alpha=alpha, values=values,
                       param="lam" if problem == "krr" else "C",
                       problem=problem, history=history,
                       metric=metric_name, converged=converged,
                       rounds_run=rounds_run, iters_run=iters_run,
                       wall_time_s=wall, comm=comm, options=opts,
                       representation=rep_name, op=rep_op)
