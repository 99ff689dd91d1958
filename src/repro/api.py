"""Unified solver facade: estimators over the paper's algorithm family.

The paper's value proposition is *same solution, tunable communication*:
classical vs s-step, block size b, and partition layout are tuning knobs
over ONE algorithm family.  This module is the single public seam that
reflects that (DESIGN.md §8):

    from repro.api import KernelSVM, KernelRidge, SolverOptions

    clf = KernelSVM(C=1.0, kernel="rbf",
                    options=SolverOptions(method="sstep", s=32,
                                          tol=1e-6, max_iters=2048))
    result = clf.fit(A, y)          # FitResult: alpha, history, comm model
    labels = clf.predict(A_test)

Dispatch covers {classical, sstep} x {serial, 1d, 2d}: the serial path
drives the shared round protocol (``core/loop.run_rounds``) directly —
one ``lax.scan`` when no tolerance/recording is requested (bit-compatible
with the legacy entrypoints), one ``lax.while_loop`` with a metric check
every ``check_every`` rounds otherwise.  The 1d/2d paths reuse the
``shard_map`` solvers in ``core/distributed``; their tolerance stopping
runs the same schedule in ``check_every``-round chunks with the metric
evaluated between chunks (round boundaries are identical because chunks
are whole multiples of s).

Convergence metrics: K-SVM stops on the duality gap
(``objectives.ksvm_duality_gap``); K-RR stops on the relative residual of
the optimality system (``objectives.krr_rel_residual``) — the paper's
rel-error needs the closed-form alpha*, which costs an m x m
factorization the facade refuses to hide inside ``fit``.

Representations (DESIGN.md §9): ``SolverOptions(approx="nystrom",
landmarks=l)`` swaps the exact kernel for a rank-l Nystrom feature map —
built ONCE per fit, consumed by the same solvers through a
``LowRankGramOperator`` (every reduction O(l)-wide; convergence metrics
evaluate under the SAME approximate kernel, so tolerance stopping stays
meaningful), and reused at predict time.  K-SVM caveat: the exact path
keeps the paper implementation's ``K(diag(y) A)`` training gram while
the low-rank path uses the textbook ``diag(y) K~ diag(y)`` (feature
scaling does not commute with nonlinear epilogues), so exact-vs-approx
K-SVM solutions are directly comparable only for linear kernels — for
K-RR (no y-scaling) the l -> m limit recovers the exact solution for
every kernel (see ``LowRankGramOperator.scale_rows``).  Prediction always runs through
the batched slab-free subsystem (``core/predict.py``): the dense
``(q x m)`` test-kernel slab of the legacy ``objectives.*_predict``
oracles never materializes.

Sweeps (DESIGN.md §10): ``fit`` takes a ``warm_start=`` alpha and
``fit_path`` solves a warm-started regularization ladder; whole grids
solve as ONE vmapped fleet via ``repro.tune.solve_fleet`` (k-fold
search: ``repro.tune.cross_validate``).  Knobs left at ``"auto"``
(``SolverOptions(s="auto", b="auto", layout="auto", approx="auto")``)
resolve through the perf-model autotuner before the solve; the chosen
``TunedPlan`` lands on ``FitResult.plan``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import enable_x64, make_mesh_auto
from repro.core import (DIVERGED_NONFINITE, GuardSpec, KernelConfig,
                        KRRConfig, SVMConfig, NO_TOL,
                        ExactGramOperator, StreamingGramOperator,
                        bdcd_krr, block_schedule, coordinate_schedule,
                        dcd_ksvm, gram_slab, krr_rel_residual,
                        ksvm_duality_gap, ksvm_duality_gap_lowrank,
                        make_bdcd_round_fn, make_dcd_round_fn,
                        make_sstep_bdcd_round_fn, make_sstep_dcd_round_fn,
                        pad_rounds, run_rounds, sstep_bdcd_krr,
                        sstep_dcd_ksvm)
from repro.core import distributed
from repro.core.nystrom import (LANDMARK_METHODS, fit_nystrom,
                                lowrank_operator)
from repro.core.perf_model import (VMEM_BYTES, choose_recompute_every,
                                   modeled_fit_cost, stream_chunk_fits,
                                   stream_working_set_bytes)
from repro.core.predict import BatchedPredictor, validate_queries
from repro.resilience.guard import (DivergenceError, finite_health,
                                    init_residual, make_correct_fn,
                                    next_fallback)
from repro.resilience.health import (HealthEvent, KIND_METRIC,
                                     KIND_NONFINITE, KIND_RESUME,
                                     SolveHealth)
from repro.resilience.checkpoint import (load_solve_state,
                                         save_solve_state,
                                         solve_fingerprint)
from repro.resilience.faults import SimulatedKill, active_plan
from repro.obs.spans import Telemetry

METHODS = ("classical", "sstep")
LAYOUTS = ("serial", "1d", "2d")
APPROX = (None, "nystrom")
AUTO = "auto"


@dataclasses.dataclass(frozen=True)
class SolverOptions:
    """How to run the solve — every knob of the paper's algorithm family.

    method:      "classical" (communicate every iteration) or "sstep"
                 (one communication round per s iterations, same iterates).
    s:           s-step depth (ignored for method="classical"), or
                 "auto" — resolved per problem by the perf-model-driven
                 autotuner (repro.tune.autotune, DESIGN.md §10) before
                 the solve; the chosen plan lands on ``FitResult.plan``.
    b:           block size (K-RR only; K-SVM is scalar-coordinate),
                 or "auto" (autotuned jointly with s).
    layout:      "serial", "1d" (paper's feature-partitioned shard_map
                 layout), "2d" (samples x features, beyond paper), or
                 "auto" (autotuned over the visible device count).
    mesh:        jax Mesh for 1d/2d; auto-built over the host's devices
                 when None ("model"-major for 1d, "data"-major for 2d).
    slab_free:   consume kernel slabs through the GramOperator (default);
                 False forces the materialized-slab parity-oracle path
                 (serial and 1d only).
    tol:         stop once the convergence metric (duality gap for K-SVM,
                 relative residual for K-RR) falls to tol; 0 disables
                 early stopping.
    check_every: metric cadence, in outer rounds.
    max_iters:   total inner-iteration budget H.  H % s != 0 is fine —
                 the final short round is handled by pad-and-mask.
    record:      keep the metric history even when tol == 0.
    seed:        PRNG seed for the coordinate/block schedule (and, folded,
                 for the landmark draw when approx is on).
    approx:      kernel representation: None (exact), "nystrom" —
                 rank-``landmarks`` feature map built once per fit, then
                 every per-round reduction runs O(landmarks)-wide through
                 a ``LowRankGramOperator`` (DESIGN.md §9) and prediction
                 serves through the same map — or "auto" (the autotuner
                 picks the cheaper modeled representation).
    landmarks:   Nystrom rank l (clipped to m at fit time).
    landmark_method: "uniform" row sampling or "kmeans" centroids.
    probe:       autotune refinement: when > 0 and any knob is "auto",
                 the top modeled candidates are additionally MEASURED
                 for ``probe`` outer rounds each and the fastest wins
                 (0 = trust the Hockney model alone).
    guard:       guarded solve (DESIGN.md §12): the round loop carries
                 the residual ``f = K @ alpha`` (same per-round kernel
                 work — the recurrence reuses the block each round
                 already evaluates), health-checks every round, corrects
                 residual drift, and on divergence auto-falls back along
                 the escalation ladder (halve s -> classical -> f64)
                 from the last good state.  ``FitResult.health`` records
                 everything observed.  Requires slab_free.
    recompute_every: drift-correction cadence in OUTER rounds — every
                 that many rounds ``f`` is recomputed exactly through
                 the operator (one extra KMV, residual replacement).
                 "auto" resolves via the perf model to the largest
                 cadence within the 10% overhead budget; 0 disables
                 correction (serial layouts only — the distributed
                 bodies recompute their round quantities from alpha
                 every round and carry no drifting residual).
    checkpoint_every: mid-solve snapshot cadence in OUTER rounds (0 =
                 off); requires ``checkpoint_dir`` and ``guard``.
                 ``fit(resume_from=checkpoint_dir)`` restores a killed
                 solve and continues it — bit-identical modulo the
                 restart round.
    checkpoint_dir: where snapshots go (atomic step directories via
                 train/checkpoint.py).
    fallback:    walk the escalation ladder on divergence (default); if
                 False a divergence raises ``DivergenceError``
                 immediately, surfacing the structured events instead.
    stream:      out-of-core representation (DESIGN.md §14): a positive
                 int streams the data through the double-buffered KMV
                 pipeline in row chunks of that size (the
                 ``StreamingGramOperator`` — no reduction ever holds X
                 or an m-tall slab in its working set); ``"auto"`` (or
                 True) lets the autotuner resolve the chunk size from
                 the streaming pipeline cost model
                 (``perf_model.choose_chunk_rows``); None/False (the
                 default) keeps the resident operator.  Exact
                 representation and serial layout only (the distributed
                 layouts shard instead of stream; low-rank factors are
                 already O(m*l)-small).
    telemetry:   observability (repro.obs, DESIGN.md §15): a
                 ``repro.obs.Telemetry`` handle — or True for a fresh
                 one — records host spans around every fit phase (each
                 also a profiler annotation ``repro.<span>``) and lands
                 on ``FitResult.telemetry`` for the trace exporter.
                 The compiled program is the same with telemetry on,
                 off (None, the default) or disabled: the round's
                 phases are named scopes in it either way.
    """

    method: str = "sstep"
    s: Union[int, str] = 16
    b: Union[int, str] = 1
    layout: str = "serial"
    mesh: Optional[object] = None
    slab_free: bool = True
    tol: float = 0.0
    check_every: int = 8
    max_iters: int = 1024
    record: bool = False
    seed: int = 0
    approx: Optional[str] = None
    landmarks: int = 256
    landmark_method: str = "uniform"
    probe: int = 0
    guard: bool = False
    recompute_every: Union[int, str] = AUTO
    checkpoint_every: int = 0
    checkpoint_dir: Optional[str] = None
    fallback: bool = True
    stream: Union[None, bool, int, str] = None
    telemetry: Union[None, bool, Telemetry] = None

    def __post_init__(self):
        # normalize the telemetry knob (True == fresh handle, False ==
        # off) and validate it eagerly like every other option
        if self.telemetry is True:
            object.__setattr__(self, "telemetry", Telemetry())
        elif self.telemetry is False:
            object.__setattr__(self, "telemetry", None)
        if self.telemetry is not None and \
                not isinstance(self.telemetry, Telemetry):
            raise ValueError(f"telemetry must be None, a bool, or a "
                             f"repro.obs.Telemetry, got "
                             f"{self.telemetry!r}")
        # normalize the stream knob first (True == "auto", False == off)
        if self.stream is True:
            object.__setattr__(self, "stream", AUTO)
        elif self.stream is False:
            object.__setattr__(self, "stream", None)
        if self.stream is not None and self.stream != AUTO and (
                not isinstance(self.stream, int) or self.stream < 1):
            raise ValueError(f"stream must be None, a positive int "
                             f"chunk size, or {AUTO!r}, got "
                             f"{self.stream!r}")
        if self.stream is not None:
            if not self.slab_free:
                raise ValueError("stream= requires slab_free=True: the "
                                 "streamed representation only exists "
                                 "behind the GramOperator interface")
            if self.layout not in ("serial", AUTO):
                raise ValueError(f"stream= requires the serial layout "
                                 f"(the distributed layouts shard the "
                                 f"data instead of streaming it), got "
                                 f"layout={self.layout!r}")
            if self.approx not in (None, AUTO):
                raise ValueError("stream= requires the exact "
                                 "representation (a low-rank factor is "
                                 "already O(m*l)-small — stream and "
                                 "approx are mutually exclusive)")
        if self.method not in METHODS:
            raise ValueError(
                f"method must be one of {METHODS}, got {self.method!r}")
        if self.layout not in LAYOUTS + (AUTO,):
            raise ValueError(f"layout must be one of "
                             f"{LAYOUTS + (AUTO,)}, got {self.layout!r}")
        for name in ("s", "b"):
            v = getattr(self, name)
            if v != AUTO and (not isinstance(v, int) or v < 1):
                raise ValueError(f"{name} must be a positive int or "
                                 f"{AUTO!r}, got {v!r}")
        for name in ("max_iters", "check_every", "landmarks"):
            v = getattr(self, name)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"{name} must be a positive int, got {v!r}")
        if not isinstance(self.probe, int) or self.probe < 0:
            raise ValueError(f"probe must be an int >= 0, "
                             f"got {self.probe!r}")
        if not self.tol >= 0.0:
            raise ValueError(f"tol must be >= 0, got {self.tol!r}")
        if not self.slab_free and self.layout == "2d":
            raise ValueError("the 2d layout is slab-free by construction; "
                             "slab_free=False is only meaningful for the "
                             "serial and 1d layouts")
        if self.approx not in APPROX + (AUTO,):
            raise ValueError(f"approx must be one of {APPROX + (AUTO,)}, "
                             f"got {self.approx!r}")
        if self.landmark_method not in LANDMARK_METHODS:
            raise ValueError(f"landmark_method must be one of "
                             f"{LANDMARK_METHODS}, got "
                             f"{self.landmark_method!r}")
        if self.recompute_every != AUTO and (
                not isinstance(self.recompute_every, int)
                or self.recompute_every < 0):
            raise ValueError(f"recompute_every must be an int >= 0 or "
                             f"{AUTO!r}, got {self.recompute_every!r}")
        if not isinstance(self.checkpoint_every, int) \
                or self.checkpoint_every < 0:
            raise ValueError(f"checkpoint_every must be an int >= 0, "
                             f"got {self.checkpoint_every!r}")
        if self.guard and not self.slab_free:
            raise ValueError("guard=True requires slab_free=True: the "
                             "guarded round protocol reads the kernel "
                             "through the GramOperator (the "
                             "materialized-slab oracle has no residual "
                             "recurrence to guard)")
        if self.checkpoint_every > 0 and self.checkpoint_dir is None:
            raise ValueError("checkpoint_every > 0 requires "
                             "checkpoint_dir=")
        if self.checkpoint_every > 0 and not self.guard:
            raise ValueError("checkpoint_every > 0 requires guard=True "
                             "(snapshots are cut at the guarded "
                             "executor's segment boundaries)")

    @property
    def needs_autotune(self) -> bool:
        """Any knob left at "auto" — ``fit`` resolves them through
        ``repro.tune.autotune`` before solving (DESIGN.md §10)."""
        return AUTO in (self.s, self.b, self.layout, self.approx,
                        self.stream)

    @property
    def s_eff(self) -> int:
        """Inner iterations per communication round (1 for classical)."""
        if self.method != "sstep":
            return 1
        if self.s == AUTO:
            raise ValueError('s="auto" is unresolved — fit() resolves it '
                             'via repro.tune.autotune.resolve_options '
                             'before solving')
        return self.s


# repro: noqa[CHK-PYTREE] host-side result record — fit() returns it to
#   the caller after every jit boundary has been crossed; it is never
#   passed back into a traced function.
@dataclasses.dataclass
class FitResult:
    """Everything ``fit`` observed: the solution, the convergence
    trajectory, and the modeled communication cost of the run."""

    alpha: jnp.ndarray
    schedule: jnp.ndarray          # the iterations actually executed —
                                   # truncated to iters_run on early stop,
                                   # so replaying it through a legacy
                                   # entrypoint reproduces alpha
    history: Optional[np.ndarray]  # metric at each check point (or None)
    metric: str                    # "duality_gap" | "rel_residual"
    converged: bool
    rounds_run: int
    iters_run: int
    wall_time_s: float
    comm: dict                     # Hockney model: flops/words/msgs/time
    options: SolverOptions         # the RESOLVED options the solve ran
                                   # with (auto knobs already concrete)
    representation: str = "exact"  # "exact" | "nystrom(l=...)"
    plan: Optional[object] = None  # tune.TunedPlan when any knob was
                                   # "auto" (modeled frontier + choice)
    health: Optional[SolveHealth] = None
                                   # guarded runs: drift observations,
                                   # divergence/fallback events,
                                   # checkpoint/resume ledger
                                   # (DESIGN.md §12)
    telemetry: Optional[Telemetry] = None
                                   # the recording handle when the fit
                                   # ran with SolverOptions(telemetry=)
                                   # — spans/marks/metrics for the
                                   # trace exporter (DESIGN.md §15)

    def metric_history(self) -> Optional[np.ndarray]:
        """The evaluated convergence trajectory — the canonical accessor
        (mirrors ``LoopResult.metric_history``): every recorded metric
        value in evaluation order, ``None`` when the run recorded none
        (``tol == 0`` and ``record=False``)."""
        return self.history


def _check_predict_batch(batch) -> int:
    """Eager validation, mirroring SolverOptions' integer knobs."""
    if not isinstance(batch, int) or batch < 1:
        raise ValueError(
            f"predict_batch must be a positive int, got {batch!r}")
    return batch


def _check_finite(value, name: str):
    """Eager input validation: reject non-finite data at the facade
    boundary with the offending argument NAMED, instead of letting a
    single NaN silently poison the whole solve through the round
    recurrences (the failure mode the runtime guard exists for —
    corrupt INPUT deserves an immediate, attributable error)."""
    value = jnp.asarray(value)
    if not jnp.issubdtype(value.dtype, jnp.floating):
        return value
    if not bool(jnp.all(jnp.isfinite(value))):
        bad = int(jnp.sum(~jnp.isfinite(value)))
        raise ValueError(
            f"{name} contains {bad} non-finite (nan/inf) value"
            f"{'s' if bad != 1 else ''} — clean or impute the data "
            f"before fitting")
    return value


def _check_positive(value: float, name: str) -> float:
    if not value > 0:
        raise ValueError(f"{name} must be > 0, got {value!r}")
    return value


def _active_tel(opts: SolverOptions) -> Optional[Telemetry]:
    """The ENABLED telemetry handle of a fit, or None — a disabled
    handle records nothing, like no handle at all."""
    t = opts.telemetry
    return t if (t is not None and t.enabled) else None


def _tspan(tel: Optional[Telemetry], name: str, phase: str, **args):
    """``tel.span(...)`` or a no-op context when telemetry is off."""
    if tel is None:
        return contextlib.nullcontext()
    return tel.span(name, phase, **args)


def _as_kernel(kernel: Union[str, KernelConfig, None]) -> KernelConfig:
    if kernel is None:
        return KernelConfig()
    if isinstance(kernel, str):
        return KernelConfig(kernel)
    return kernel


def _resolve_mesh(opts: SolverOptions):
    """User mesh (validated for the layout's axis names) or an auto mesh
    over every visible device."""
    ndev = len(jax.devices())
    if opts.mesh is None:
        shape = (1, ndev) if opts.layout == "1d" else (ndev, 1)
        return make_mesh_auto(shape, ("data", "model"))
    need = ("model",) if opts.layout == "1d" else ("data", "model")
    missing = [ax for ax in need if ax not in opts.mesh.axis_names]
    if missing:
        raise ValueError(f"mesh lacks axes {missing} required by the "
                         f"{opts.layout!r} layout (has "
                         f"{opts.mesh.axis_names})")
    return opts.mesh


@partial(jax.jit, static_argnames=("cfg", "s", "check_every", "slab_free",
                                   "lowrank"))
def _ksvm_serial_tol(A, y, a0, schedule, tol, *, cfg: SVMConfig, s: int,
                     check_every: int, slab_free: bool, op=None,
                     lowrank: bool = False):
    gram = None if slab_free else gram_slab
    op = None if gram is not None else op
    if s == 1:
        rf, xs = make_dcd_round_fn(A, y, cfg, gram_fn=gram, op=op), schedule
    else:
        rf = make_sstep_dcd_round_fn(A, y, cfg, s, gram_fn=gram, op=op)
        xs = pad_rounds(schedule, s)
    # low-rank runs (A is Phi) check the gap through the O(m l) factored
    # form — the generic oracle would build the m x m gram of Phi
    metric = (ksvm_duality_gap_lowrank if lowrank else ksvm_duality_gap)
    return run_rounds(rf, a0, xs, tol=tol, check_every=check_every,
                      metric_fn=lambda a: metric(A, y, a, cfg))


@partial(jax.jit, static_argnames=("cfg", "s", "check_every", "slab_free"))
def _krr_serial_tol(A, y, a0, schedule, tol, *, cfg: KRRConfig, s: int,
                    check_every: int, slab_free: bool, op=None):
    gram = None if slab_free else gram_slab
    op = None if gram is not None else op
    if s == 1:
        rf, xs = make_bdcd_round_fn(A, y, cfg, gram_fn=gram, op=op), schedule
    else:
        rf = make_sstep_bdcd_round_fn(A, y, cfg, s, gram_fn=gram, op=op)
        xs = pad_rounds(schedule, s)
    return run_rounds(rf, a0, xs, tol=tol, check_every=check_every,
                      metric_fn=lambda a: krr_rel_residual(A, y, a, cfg))


@partial(jax.jit, static_argnames=("problem", "cfg", "s", "check_every",
                                   "correct_every", "lowrank",
                                   "want_metric", "fault_target"))
def _guarded_serial_chunk(A, y, a0, f0, schedule, tol, fault_round,
                          fault_value, *, problem, cfg, s: int,
                          check_every: int, correct_every: int,
                          lowrank: bool, want_metric: bool,
                          fault_target: Optional[str] = None, op=None):
    """One guarded segment (DESIGN.md §12): the guarded round fns over
    the ``(alpha, f)`` carry, driven by the guarded while-loop with
    per-round health checks and periodic residual replacement.  The
    fault lane (static ``fault_target``) is the test harness's hook: at
    round ``fault_round`` it adds ``fault_value`` to the chosen carry
    leaf AFTER the round update — the jit-safe analogue of a hardware
    flip, compiled only when a fault plan is armed."""
    if problem == "ksvm":
        if s == 1:
            base, xs = make_dcd_round_fn(A, y, cfg, op=op,
                                         guard=True), schedule
        else:
            base = make_sstep_dcd_round_fn(A, y, cfg, s, op=op,
                                           guard=True)
            xs = pad_rounds(schedule, s)
        gap = ksvm_duality_gap_lowrank if lowrank else ksvm_duality_gap
        metric = lambda c: gap(A, y, c[0], cfg)
    else:
        if s == 1:
            base, xs = make_bdcd_round_fn(A, y, cfg, op=op,
                                          guard=True), schedule
        else:
            base = make_sstep_bdcd_round_fn(A, y, cfg, s, op=op,
                                            guard=True)
            xs = pad_rounds(schedule, s)
        metric = lambda c: krr_rel_residual(A, y, c[0], cfg)

    rf = base
    if fault_target is not None:
        R = schedule.shape[0] if s == 1 else -(-schedule.shape[0] // s)
        hits = jnp.arange(R) == fault_round

        def rf(carry, xz):
            x, hit = xz
            alpha, f = base(carry, x)
            bad = jnp.where(hit, jnp.asarray(fault_value, alpha.dtype),
                            jnp.zeros((), alpha.dtype))
            if fault_target == "alpha":
                return alpha + bad, f
            return alpha, f + bad

        xs = (xs, hits)

    spec = GuardSpec(
        health_fn=finite_health,
        correct_fn=make_correct_fn(op) if correct_every >= 1 else None,
        correct_every=correct_every)
    return run_rounds(rf, (a0, f0), xs, tol=tol, check_every=check_every,
                      metric_fn=metric if want_metric else None,
                      guard=spec)


# Backends whose compiler cannot build the f64 rung: XLA:TPU implements
# LU decomposition for F32/C64 only, so the K-RR block solve in f64 is
# refused at compile time (seen on a TPU v5e with jax 0.9.0).
F64_UNSUPPORTED_PLATFORMS = ("tpu",)


def _require_f64(x, events) -> None:
    """Stop the ladder with an error naming the device when the f64 rung
    cannot run where ``x`` lives, instead of failing in the compiler."""
    dev = next(iter(x.devices()))
    if dev.platform in F64_UNSUPPORTED_PLATFORMS:
        raise DivergenceError(
            f"guarded solve diverged and the escalation ladder reached its "
            f"f64 rung, which {dev.platform} device {dev.device_kind!r} "
            f"cannot compile (no f64 linear solve on this backend); rerun "
            f"with a smaller s, a larger regularization, or on a CPU",
            events=tuple(events))


def _cast_floating(tree, dtype):
    """Cast every floating leaf (operators are registered pytrees, so
    their static config rides along untouched)."""
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x,
        tree)


def _run_guarded_serial(problem, A_s, y, a0, schedule, cfg_s,
                        opts: SolverOptions, train_op, *, fingerprint,
                        resume=None):
    """The host half of the guarded serial solve: run
    ``_guarded_serial_chunk`` in checkpoint-bounded segments, harvest
    drift/metric observations, and on divergence walk the escalation
    ladder (halve s -> classical -> f64 accumulation) from the last
    good state.  Returns ``(alpha, history, converged, rounds_run,
    iters_run, health)``."""
    from repro.train.checkpoint import CheckpointManager

    H = schedule.shape[0]
    want_metric = opts.tol > 0.0 or opts.record
    tol = opts.tol if opts.tol > 0.0 else NO_TOL
    lowrank = problem == "ksvm" and bool(opts.approx)
    base_dtype = A_s.dtype
    tel = _active_tel(opts)

    s_cur, method_cur = opts.s_eff, opts.method
    x64 = False
    pos, rounds_done, converged = 0, 0, False
    alpha = a0
    f = None
    events, drifts, hists = [], [], []
    checkpoints, resumed_from = 0, None

    if resume is not None:
        alpha = jnp.asarray(resume["alpha"], base_dtype)
        f = (jnp.asarray(resume["f"], base_dtype)
             if resume.get("f") is not None else None)
        pos = resume["iters_done"]
        s_cur, method_cur = resume["s_cur"], resume["method_cur"]
        resumed_from = resume["path"]
        events.append(HealthEvent(
            kind=KIND_RESUME, round_idx=rounds_done, iter_idx=pos,
            action="resume", detail=resumed_from))

    plan = active_plan()
    mgr = None
    if opts.checkpoint_every > 0:
        mgr = CheckpointManager(opts.checkpoint_dir, save_every=1)

    A_cur, y_cur, op_cur = A_s, y, train_op
    if f is None:
        f = init_residual(op_cur, alpha)

    while pos < H and not converged:
        if opts.checkpoint_every > 0:
            seg = min(opts.checkpoint_every * s_cur, H - pos)
        else:
            seg = H - pos
        sched_seg = schedule[pos:pos + seg]
        fault_round = (plan.carry_fault_round(pos, seg, s_cur)
                       if plan is not None else -1)
        fault_target = plan.target if fault_round >= 0 else None
        fault_value = plan.value if plan is not None else float("nan")

        ctx = enable_x64() if x64 else contextlib.nullcontext()
        with ctx, _tspan(tel, "guarded_segment", "solve", iter_start=pos,
                         iters=int(seg), s=s_cur):
            res = _guarded_serial_chunk(
                A_cur, y_cur, alpha, f, sched_seg,
                jnp.asarray(tol, A_cur.dtype), fault_round, fault_value,
                problem=problem, cfg=cfg_s, s=s_cur,
                check_every=opts.check_every,
                correct_every=opts.recompute_every,
                lowrank=lowrank, want_metric=want_metric,
                fault_target=fault_target, op=op_cur)
            # the segment boundary is already a sync point (the host
            # branches on diverged_round next); syncing INSIDE the span
            # keeps the measured interval honest
            div = int(res.diverged_round)
        dh = res.drift_history()
        if dh is not None and len(dh):
            drifts.append(np.asarray(dh, np.float64))
            if tel is not None:
                tel.metrics.counter(
                    "repro_guard_corrections_total",
                    "residual drift corrections applied").inc(len(dh))
        mh = res.metric_history()
        if mh is not None and len(mh):
            hists.append(np.asarray(mh, np.float64))

        if div >= 0:
            # the unhealthy round's update was DISCARDED in-loop; the
            # carry is the last good state — consume the good prefix
            alpha, f = res.state
            good = div
            consumed = min(good * s_cur, seg)
            pos += consumed
            rounds_done += good
            kind = (KIND_NONFINITE
                    if int(res.diverged_kind) == DIVERGED_NONFINITE
                    else KIND_METRIC)
            if fault_round >= 0 and div >= fault_round:
                plan.carry_fired = True      # one-shot: don't re-fire
            if not opts.fallback:
                raise DivergenceError(
                    f"guarded solve diverged ({kind}) at round "
                    f"{rounds_done} (iteration {pos}) and fallback is "
                    f"disabled", events=tuple(events))
            try:
                action, s_cur, method_cur, x64_new = next_fallback(
                    s_cur, method_cur, x64)
            except DivergenceError as e:
                raise DivergenceError(str(e),
                                      events=tuple(events)) from None
            events.append(HealthEvent(
                kind=kind, round_idx=rounds_done, iter_idx=pos,
                action=action,
                detail=f"resuming from last good state at iter {pos}"))
            if tel is not None:
                tel.metrics.counter(
                    "repro_guard_fallbacks_total",
                    "escalation-ladder steps taken").inc(
                        action=action, kind=kind)
                tel.mark("fallback", phase="guard")
            if x64_new and not x64:
                _require_f64(A_cur, events)
                x64 = True
                with enable_x64():
                    A_cur = A_cur.astype(jnp.float64)
                    y_cur = y_cur.astype(jnp.float64)
                    op_cur = _cast_floating(op_cur, jnp.float64)
                    alpha = alpha.astype(jnp.float64)
            # after ANY event the recurrence restarts from an exact
            # residual (the fault may have corrupted f alone)
            with (enable_x64() if x64 else contextlib.nullcontext()):
                f = op_cur.full_matvec(alpha)
            continue

        alpha, f = res.state
        seg_rounds = int(res.rounds_run)
        rounds_done += seg_rounds
        if bool(res.converged):
            converged = True
            pos += min(seg_rounds * s_cur, seg)
        else:
            pos += seg
        if mgr is not None and not converged and pos < H:
            save_solve_state(mgr, pos,
                             jnp.asarray(alpha, base_dtype),
                             jnp.asarray(f, base_dtype),
                             s_cur=s_cur, method_cur=method_cur,
                             fingerprint=fingerprint)
            checkpoints += 1
            if plan is not None and plan.should_kill(pos):
                plan.kill_fired = True
                mgr.wait()               # the snapshot is durable
                raise SimulatedKill(
                    f"simulated preemption at iteration {pos}",
                    opts.checkpoint_dir)
    if mgr is not None:
        mgr.wait()

    if x64:
        with enable_x64():
            alpha = alpha.astype(base_dtype)

    history = (np.concatenate(hists) if hists
               else (np.zeros(0) if want_metric else None))
    health = SolveHealth(
        guarded=True, recompute_every=opts.recompute_every,
        drift=(np.concatenate(drifts) if drifts else np.zeros(0)),
        corrections=sum(len(d) for d in drifts),
        events=tuple(events), checkpoints=checkpoints,
        resumed_from=resumed_from)
    return alpha, history, converged, rounds_done, pos, health


def _run_guarded_dist(problem, A_s, y, a0, schedule, cfg_s,
                      opts: SolverOptions, mesh, metric_host, *,
                      fingerprint, resume=None):
    """Guarded executor for the 1d/2d layouts.  The distributed bodies
    recompute their round quantities from alpha every round (one psum —
    audited by repro.analysis.comm_check), so there is NO drifting
    residual to correct and NO extra in-loop collective the guard could
    add; the guard runs at chunk boundaries on the host instead:
    non-finite/blown-up alpha detection, the same escalation ladder
    (from the chunk-start state), and checkpoint/resume.  Returns
    ``(alpha, history, converged, rounds_run, iters_run, health)``."""
    from repro.train.checkpoint import CheckpointManager
    from repro.resilience.faults import poisoned_1d_factory

    H = schedule.shape[0]
    want_metric = opts.tol > 0.0 or opts.record
    base_dtype = A_s.dtype
    tel = _active_tel(opts)
    blowup = 1e4

    s_cur, method_cur = opts.s_eff, opts.method
    x64 = False
    pos, rounds_done, converged = 0, 0, False
    alpha = a0
    events, hist = [], []
    checkpoints, resumed_from = 0, None
    best = float("inf")

    if resume is not None:
        alpha = jnp.asarray(resume["alpha"], base_dtype)
        pos = resume["iters_done"]
        s_cur, method_cur = resume["s_cur"], resume["method_cur"]
        resumed_from = resume["path"]
        events.append(HealthEvent(
            kind=KIND_RESUME, round_idx=rounds_done, iter_idx=pos,
            action="resume", detail=resumed_from))

    plan = active_plan()
    mgr = None
    if opts.checkpoint_every > 0:
        mgr = CheckpointManager(opts.checkpoint_dir, save_every=1)
    A_cur, y_cur = A_s, y

    while pos < H and not converged:
        chunk = opts.check_every * s_cur
        if opts.checkpoint_every > 0:
            chunk = min(chunk, opts.checkpoint_every * s_cur)
        seg = min(chunk, H - pos)
        sched_seg = schedule[pos:pos + seg]
        # 1d fault harness: a poisoned op_factory corrupts one rank's
        # psum contribution for the whole chunk containing the target
        # iteration (consumed once, like the serial fault lane)
        op_factory = None
        if (plan is not None and opts.layout == "1d"
                and plan.carry_fault_round(pos, seg, s_cur) >= 0):
            op_factory = poisoned_1d_factory(scale=plan.value)
        ctx = enable_x64() if x64 else contextlib.nullcontext()
        with ctx, _tspan(tel, "guarded_chunk", "solve", iter_start=pos,
                         iters=int(seg), s=s_cur, layout=opts.layout):
            alpha_new = _dist_chunk(A_cur, y_cur, alpha, sched_seg,
                                    problem=problem, layout=opts.layout,
                                    mesh=mesh, cfg=cfg_s, s=s_cur,
                                    slab_free=opts.slab_free,
                                    op_factory=op_factory)
            # the finiteness probe is the chunk's existing sync point;
            # syncing inside the span keeps the interval honest
            healthy = bool(jnp.all(jnp.isfinite(alpha_new)))
        val = None
        kind = KIND_NONFINITE
        if healthy and want_metric:
            val = metric_host(alpha_new)
            if not np.isfinite(val) or (np.isfinite(best)
                                        and val > blowup * best):
                healthy, kind = False, KIND_METRIC

        if not healthy:
            # last good state = the chunk-start alpha (the distributed
            # body is one jit region; mid-chunk rounds are not
            # recoverable — chunks are the guard granularity here)
            if op_factory is not None:
                plan.carry_fired = True
            if not opts.fallback:
                raise DivergenceError(
                    f"guarded {opts.layout} solve diverged ({kind}) in "
                    f"the chunk at iteration {pos} and fallback is "
                    f"disabled", events=tuple(events))
            try:
                action, s_cur, method_cur, x64_new = next_fallback(
                    s_cur, method_cur, x64)
            except DivergenceError as e:
                raise DivergenceError(str(e),
                                      events=tuple(events)) from None
            events.append(HealthEvent(
                kind=kind, round_idx=rounds_done, iter_idx=pos,
                action=action,
                detail=f"re-running chunk from iteration {pos}"))
            if tel is not None:
                tel.metrics.counter(
                    "repro_guard_fallbacks_total",
                    "escalation-ladder steps taken").inc(
                        action=action, kind=kind)
                tel.mark("fallback", phase="guard")
            if x64_new and not x64:
                _require_f64(A_cur, events)
                x64 = True
                with enable_x64():
                    A_cur = A_cur.astype(jnp.float64)
                    y_cur = y_cur.astype(jnp.float64)
                    alpha = alpha.astype(jnp.float64)
            continue

        alpha = alpha_new
        pos += seg
        rounds_done += -(-seg // s_cur)
        if val is not None:
            hist.append(val)
            best = min(best, val)
            if opts.tol > 0.0 and val <= opts.tol:
                converged = True
        if mgr is not None and not converged and pos < H:
            save_solve_state(mgr, pos, jnp.asarray(alpha, base_dtype),
                             None, s_cur=s_cur, method_cur=method_cur,
                             fingerprint=fingerprint)
            checkpoints += 1
            if plan is not None and plan.should_kill(pos):
                plan.kill_fired = True
                mgr.wait()
                raise SimulatedKill(
                    f"simulated preemption at iteration {pos}",
                    opts.checkpoint_dir)
    if mgr is not None:
        mgr.wait()

    if x64:
        with enable_x64():
            alpha = alpha.astype(base_dtype)
    history = np.asarray(hist) if want_metric else None
    health = SolveHealth(
        guarded=True, recompute_every=0, drift=np.zeros(0),
        corrections=0, events=tuple(events), checkpoints=checkpoints,
        resumed_from=resumed_from)
    return alpha, history, converged, rounds_done, pos, health


def _serial_fast(problem, A, y, a0, schedule, cfg, s, slab_free, op=None):
    """tol == 0, no recording: the legacy jitted entrypoints verbatim
    (driven by the facade-built operator when slab-free)."""
    gram = None if slab_free else gram_slab
    op = None if gram is not None else op
    if problem == "ksvm":
        if s == 1:
            return dcd_ksvm(A, y, a0, schedule, cfg, gram_fn=gram,
                            op=op)[0]
        return sstep_dcd_ksvm(A, y, a0, schedule, cfg, s, gram_fn=gram,
                              op=op)[0]
    if s == 1:
        return bdcd_krr(A, y, a0, schedule, cfg, gram_fn=gram, op=op)[0]
    return sstep_bdcd_krr(A, y, a0, schedule, cfg, s, gram_fn=gram,
                          op=op)[0]


@partial(jax.jit, static_argnames=("problem", "layout", "mesh", "cfg",
                                   "s", "slab_free", "op_factory"))
def _dist_chunk(A, y, a0, schedule, *, problem, layout, mesh, cfg, s,
                slab_free, op_factory=None):
    """Jit-cached wrapper around the shard_map solvers: the chunked
    tolerance loop re-enters here once per chunk, and every chunk of the
    same length hits the cache instead of re-tracing the shard_map body
    (at most two shapes compile per fit: the chunk and the ragged tail).
    ``op_factory`` (static) overrides the per-rank operator build — the
    fault-injection hook for guarded distributed runs."""
    return _dist_call(problem, layout, mesh, A, y, a0, schedule, cfg, s,
                      slab_free, op_factory)


def _dist_call(problem, layout, mesh, A, y, a0, schedule, cfg, s,
               slab_free, op_factory=None):
    if problem == "ksvm":
        if layout == "1d":
            return distributed.dist_sstep_dcd_ksvm(
                mesh, A, y, a0, schedule, cfg, s=s, slab_free=slab_free,
                op_factory=op_factory)
        return distributed.dist_sstep_dcd_ksvm_2d(
            mesh, A, y, a0, schedule, cfg, s=s, op_factory=op_factory)
    if layout == "1d":
        return distributed.dist_sstep_bdcd_krr(
            mesh, A, y, a0, schedule, cfg, s=s, slab_free=slab_free,
            op_factory=op_factory)
    return distributed.dist_sstep_bdcd_krr_2d(
        mesh, A, y, a0, schedule, cfg, s=s, op_factory=op_factory)


def _build_representation(A, cfg, opts: SolverOptions):
    """The once-per-fit representation build (DESIGN.md §9): returns
    ``(op, A_solve)`` where ``op`` is the raw-data ``GramOperator`` the
    estimator keeps for prediction and ``A_solve`` is the data the
    solvers run on — ``A`` for exact, ``Phi`` for Nystrom (the same
    solvers then perform O(landmarks)-wide reductions; the s-step
    schedule is untouched).  Pair with ``_solve_cfg`` for the matching
    solver config; warm-started paths and fleets (repro.tune) build
    this ONCE and reuse it across every solve in the sweep.

    The landmark draw folds ``opts.seed`` (like the schedule key), so
    Nystrom fits — uniform OR kmeans landmarks — are reproducible
    end-to-end from the single facade seed."""
    if opts.approx is None:
        if opts.stream:
            if opts.stream == AUTO:
                raise ValueError('stream="auto" is unresolved — fit() '
                                 'resolves it via repro.tune.autotune.'
                                 'resolve_options before building the '
                                 'representation')
            m, n = A.shape
            sb = opts.s_eff * (opts.b if isinstance(cfg, KRRConfig) else 1)
            cr = min(int(opts.stream), m)
            word = A.dtype.itemsize
            if not stream_chunk_fits(cr, n, sb, word=word):
                raise ValueError(
                    f"stream={opts.stream} does not fit the VMEM budget of "
                    f"{VMEM_BYTES} bytes at n={n}, s*b={sb}: the streamed "
                    f"KMV's double-buffered working set is "
                    f"{stream_working_set_bytes(cr, n, sb, word=word)} "
                    f"bytes; pin a smaller chunk or use stream='auto'")
            return (StreamingGramOperator.from_dense(
                A, cfg.kernel, chunk_rows=int(opts.stream)), A)
        return ExactGramOperator(A, cfg.kernel), A
    l = min(opts.landmarks, A.shape[0])
    lkey = jax.random.fold_in(jax.random.key(opts.seed), 1)
    fmap = fit_nystrom(lkey, A, cfg.kernel, l,
                       method=opts.landmark_method)
    op = lowrank_operator(fmap, A)
    return op, op.Phi


def _solve_cfg(cfg, opts: SolverOptions):
    """The config the solvers and convergence metrics run on: ``cfg``
    itself for exact, the linear-kernel replacement for low-rank runs
    (the factor Phi already carries the nonlinearity).  Cheap — safe to
    recompute per solve while the operator is reused (reg_path)."""
    if opts.approx is None:
        return cfg
    return dataclasses.replace(cfg, kernel=KernelConfig("linear"))


def _fit(problem: str, A, y, cfg, opts: SolverOptions, *,
         a0=None, rep=None, resume_from=None):
    """Telemetry shell around ``_fit_body``: when the fit carries an
    enabled handle, bracket the whole call in one phase="fit" span."""
    tel = _active_tel(opts)
    if tel is None:
        return _fit_body(problem, A, y, cfg, opts, a0=a0, rep=rep,
                         resume_from=resume_from)
    with tel.span("fit", phase="fit", problem=problem,
                  m=int(A.shape[0]), n=int(A.shape[1])):
        return _fit_body(problem, A, y, cfg, opts, a0=a0, rep=rep,
                         resume_from=resume_from)


def _fit_body(problem: str, A, y, cfg, opts: SolverOptions, *,
              a0=None, rep=None, resume_from=None):
    m, n = A.shape

    plan = None
    if opts.needs_autotune:
        from repro.tune.autotune import resolve_options
        plan = resolve_options(m, n, cfg, opts, problem=problem,
                               A=A, y=y)
        opts = plan.options
    if opts.guard and opts.recompute_every == AUTO:
        # idempotent backstop behind autotune's own resolution: price the
        # exact recompute against the per-round cost and pick the cadence
        # that keeps guarded overhead under GUARD_OVERHEAD_BUDGET.  The
        # distributed layouts recompute from alpha every round already —
        # no drifting residual, so correction is off there.
        if opts.layout == "serial":
            rec = choose_recompute_every(
                m, n, cfg.kernel.name,
                b=opts.b if problem == "krr" else 1, s=opts.s_eff,
                approx=bool(opts.approx),
                landmarks=min(opts.landmarks, m) if opts.approx else 0)
        else:
            rec = 0
        opts = dataclasses.replace(opts, recompute_every=rec)
    if resume_from is not None and not opts.guard:
        raise ValueError("resume_from= requires options.guard=True (the "
                         "checkpoint holds a guarded-carry snapshot)")

    H = opts.max_iters
    s = opts.s_eff
    b = opts.b if problem == "krr" else 1
    key = jax.random.key(opts.seed)
    # re-resolve after the autotune replace: the handle rides on opts
    tel = _active_tel(opts)

    t0 = time.perf_counter()
    # representation build (inside the clock: it is part of the solve
    # cost, mirrored by comm["setup_time"] in the Hockney model) —
    # unless a prebuilt representation is injected (warm-started paths
    # amortize ONE build across the whole ladder)
    if rep is None:
        with _tspan(tel, "representation_build", "setup",
                    approx=bool(opts.approx)):
            rep = _build_representation(A, cfg, opts)
            # drain the async dispatch inside the span so the setup
            # phase owns its own cost (not the first solve chunk's)
            if tel is not None:
                jax.block_until_ready(rep[1])
    rep_op, A_s = rep
    cfg_s = _solve_cfg(cfg, opts)
    # like representation_build, the schedule and train_op spans block
    # on their outputs (telemetry on only), so each times its own work
    with _tspan(tel, "schedule", "setup"):
        schedule = (coordinate_schedule(key, H, m) if problem == "ksvm"
                    else block_schedule(key, H, m, b))
        if tel is not None:
            jax.block_until_ready(schedule)
    if problem == "ksvm":
        metric_name = "duality_gap"
        gap = (ksvm_duality_gap_lowrank if opts.approx
               else ksvm_duality_gap)
        metric_host = lambda a: float(gap(A_s, y, a, cfg_s))
    else:
        metric_name = "rel_residual"
        # under approx, cfg_s is linear, so the residual's kernel matvec
        # contracts algebraically (kmv_slab_free linear branch:
        # Phi @ (Phi^T alpha)) — already O(m l), no factored twin needed
        metric_host = lambda a: float(krr_rel_residual(A_s, y, a, cfg_s))
    # warm start (repro.tune paths): replaying FitResult.schedule from
    # the SAME a0 reproduces alpha, so warm-started results stay
    # replayable — the schedule contract is unchanged
    a0 = (jnp.zeros(m, A.dtype) if a0 is None
          else jnp.asarray(a0, A.dtype))
    want_metric = opts.tol > 0.0 or opts.record
    tol = opts.tol if opts.tol > 0.0 else NO_TOL

    resume = None
    fp = None
    if opts.guard:
        fp = solve_fingerprint(problem, m, A.dtype, cfg, opts)
        if resume_from is not None:
            r_alpha, r_f, extra = load_solve_state(
                resume_from, expect_fingerprint=fp)
            resume = {"alpha": r_alpha, "f": r_f,
                      "iters_done": int(extra["iters_done"]),
                      "s_cur": int(extra["s_cur"]),
                      "method_cur": extra["method_cur"],
                      "path": resume_from}

    history = None
    converged = False
    health = None
    if opts.layout == "serial":
        P = 1
        # the training operator (K-SVM: diag(y)-scaled rows — a second
        # (m, n)/(m, l) buffer) is built ONLY where it is consumed: the
        # serial slab-free paths.  The shard_map bodies rebuild their
        # per-rank operators from their own shards, and the
        # materialized-slab oracle bypasses operators entirely.
        train_op = rep_op if opts.slab_free else None
        if opts.slab_free and problem == "ksvm":
            with _tspan(tel, "train_op", "setup"):
                train_op = rep_op.scale_rows(y)
                if tel is not None:
                    jax.block_until_ready(train_op)
        # zero rows the solve appends to A, once, to whole KMV blocks
        pad_rows = 0 if train_op is None else train_op.round_pad_rows
        if opts.guard:
            (alpha, history, converged, rounds_run, iters_run,
             health) = _run_guarded_serial(
                problem, A_s, y, a0, schedule, cfg_s, opts, train_op,
                fingerprint=fp, resume=resume)
        elif not want_metric:
            # the span brackets dispatch + completion
            with _tspan(tel, "solve", "solve", path="fast", s=s, b=b,
                        pad_rows=pad_rows):
                alpha = _serial_fast(problem, A_s, y, a0, schedule,
                                     cfg_s, s, opts.slab_free,
                                     op=train_op)
                if tel is not None:
                    jax.block_until_ready(alpha)
            rounds_run = -(-H // s)
        else:
            kw = ({"lowrank": bool(opts.approx)} if problem == "ksvm"
                  else {})
            solve = (_ksvm_serial_tol if problem == "ksvm"
                     else _krr_serial_tol)
            with _tspan(tel, "solve", "solve", path="tol", s=s, b=b,
                        pad_rows=pad_rows):
                res = solve(A_s, y, a0, schedule, tol, cfg=cfg_s, s=s,
                            check_every=opts.check_every,
                            slab_free=opts.slab_free, op=train_op, **kw)
                alpha = res.state
                # rounds_run is the host sync; inside the span so the
                # measured interval covers the whole while-loop
                rounds_run = int(res.rounds_run)
            converged = bool(res.converged)
            history = np.asarray(res.metric_history())
        if not opts.guard:
            iters_run = min(rounds_run * s, H)
    else:
        # the shard_map bodies build their own per-rank operators from
        # the sharded solve matrix: for low-rank runs A_s IS Phi, so the
        # 1d layout shards Phi's l columns (and the linear-kernel psum
        # payload shrinks to the contracted (sb, sb+1) words).
        mesh = _resolve_mesh(opts)
        P = (mesh.shape["model"] if opts.layout == "1d"
             else mesh.shape["data"] * mesh.shape["model"])
        alpha = a0
        dist_kw = dict(problem=problem, layout=opts.layout, mesh=mesh,
                       cfg=cfg_s, s=s, slab_free=opts.slab_free)
        if opts.guard:
            (alpha, history, converged, rounds_run, iters_run,
             health) = _run_guarded_dist(
                problem, A_s, y, a0, schedule, cfg_s, opts, mesh,
                metric_host, fingerprint=fp, resume=resume)
        elif not want_metric:
            with _tspan(tel, "solve", "solve", path="dist_fast", s=s, b=b,
                        layout=opts.layout):
                alpha = _dist_chunk(A_s, y, alpha, schedule, **dist_kw)
                if tel is not None:
                    jax.block_until_ready(alpha)
            rounds_run, iters_run = -(-H // s), H
        else:
            # chunked early stopping: whole multiples of s per chunk keep
            # the round decomposition identical to the unchunked run.
            chunk = opts.check_every * s
            pos, rounds_run, hist = 0, 0, []
            while pos < H:
                sched_c = schedule[pos:pos + chunk]
                with _tspan(tel, "dist_chunk", "solve", iter_start=pos,
                            iters=int(sched_c.shape[0]), s=s,
                            layout=opts.layout):
                    alpha = _dist_chunk(A_s, y, alpha, sched_c,
                                        **dist_kw)
                    pos += sched_c.shape[0]
                    rounds_run += -(-sched_c.shape[0] // s)
                    # the metric read is the chunk's existing sync point
                    val = metric_host(alpha)
                hist.append(val)
                if opts.tol > 0.0 and val <= opts.tol:
                    converged = True
                    break
            iters_run = pos
            history = np.asarray(hist)
    jax.block_until_ready(alpha)
    wall = time.perf_counter() - t0

    l = A_s.shape[1] if opts.approx else 0
    comm = modeled_fit_cost(m, n, cfg.kernel.name, b=b, s=s,
                            iters=iters_run, P=P, approx=opts.approx,
                            landmarks=l)
    rep_name = f"nystrom(l={l})" if opts.approx else "exact"
    result = FitResult(alpha=alpha, schedule=schedule[:iters_run],
                       history=history, metric=metric_name,
                       converged=converged,
                       rounds_run=rounds_run, iters_run=iters_run,
                       wall_time_s=wall, comm=comm, options=opts,
                       representation=rep_name, plan=plan, health=health,
                       telemetry=tel)
    return result, rep_op


class KernelSVM:
    """Kernel SVM solved by (s-step) Dual Coordinate Descent.

    Estimator facade over ``core.dcd`` / ``core.sstep_dcd`` and their
    shard_map layouts; see module docstring and ``SolverOptions``.

    ``fit`` builds the kernel representation (a ``GramOperator``: exact
    or Nystrom low-rank per ``options.approx``) ONCE and keeps it on
    ``op_``; ``decision_function``/``predict`` serve through the same
    operator with the batched slab-free subsystem (``core/predict.py``),
    after compacting the model to its support vectors.
    """

    def __init__(self, C: float = 1.0, loss: str = "l1",
                 kernel: Union[str, KernelConfig, None] = None,
                 options: Optional[SolverOptions] = None,
                 predict_batch: int = 1024):
        _check_positive(C, "C")
        self.cfg = SVMConfig(C=C, loss=loss, kernel=_as_kernel(kernel))
        self.options = options or SolverOptions()
        self.predict_batch = _check_predict_batch(predict_batch)

    def fit(self, A, y, warm_start=None, resume_from=None) -> FitResult:
        """Solve the dual.  ``warm_start`` seeds alpha (shape (m,)) —
        e.g. the solution at a neighbouring C (see ``fit_path``);
        ``None`` is the usual cold start at zero.  ``resume_from``
        restores a mid-solve checkpoint directory written by a guarded
        fit (``options.checkpoint_every``) and continues from it."""
        _check_finite(A, "A")
        _check_finite(y, "y")
        result, op = _fit("ksvm", A, y, self.cfg, self.options,
                          a0=warm_start, resume_from=resume_from)
        self.A_, self.y_, self.alpha_ = A, y, result.alpha
        self.op_ = op
        self.result_ = result
        self._predictor = None
        return result

    def fit_path(self, A, y, Cs):
        """Warm-started solve ladder over a C grid
        (``repro.tune.path.reg_path``, DESIGN.md §10): one shared
        representation build, each solve seeded from its neighbour.
        Returns a ``PathResult``; the estimator is left fitted at the
        ladder's final (largest-C, least-regularized) member."""
        from repro.tune.path import reg_path
        path = reg_path(A, y, Cs=Cs, cfg=self.cfg, options=self.options)
        last = path.results[-1]
        self.cfg = dataclasses.replace(self.cfg, C=float(path.values[-1]))
        self.A_, self.y_, self.alpha_ = A, y, last.alpha
        self.op_ = path.op
        self.result_ = last
        self._predictor = None
        return path

    def decision_function(self, A_test):
        A_test = validate_queries(self.op_, A_test, name="A_test")
        _check_finite(A_test, "A_test")
        if self._predictor is None:
            self._predictor = BatchedPredictor(
                self.op_, self.alpha_ * self.y_,
                batch=self.predict_batch, compact=True)
        return self._predictor(A_test)

    def predict(self, A_test):
        return jnp.sign(self.decision_function(A_test))

    def save(self, directory: str) -> str:
        """Persist the fitted model as a serving artifact
        (``repro.serve.artifacts.save_model``, DESIGN.md §13): restore
        with ``repro.serve.load_model`` / ``ModelRegistry.load`` — no
        refit, no live estimator needed.  Returns the artifact path."""
        from repro.serve.artifacts import save_model
        return save_model(directory, self)


class KernelRidge:
    """Kernel ridge regression solved by (s-step) Block Dual Coordinate
    Descent.  Estimator facade over ``core.bdcd`` / ``core.sstep_bdcd``
    and their shard_map layouts; see module docstring and
    ``SolverOptions``.

    Like ``KernelSVM``, ``fit`` builds the representation operator once
    (``op_``) and ``predict`` serves through it batched and slab-free.
    """

    def __init__(self, lam: float = 1.0,
                 kernel: Union[str, KernelConfig, None] = None,
                 options: Optional[SolverOptions] = None,
                 predict_batch: int = 1024):
        _check_positive(lam, "lam")
        self.cfg = KRRConfig(lam=lam, kernel=_as_kernel(kernel))
        self.options = options or SolverOptions()
        self.predict_batch = _check_predict_batch(predict_batch)

    def fit(self, A, y, warm_start=None, resume_from=None) -> FitResult:
        """Solve the dual.  ``warm_start`` seeds alpha (shape (m,)) —
        e.g. the solution at a neighbouring lambda (see ``fit_path``);
        ``None`` is the usual cold start at zero.  ``resume_from``
        restores a mid-solve checkpoint directory written by a guarded
        fit (``options.checkpoint_every``) and continues from it."""
        _check_finite(A, "A")
        _check_finite(y, "y")
        result, op = _fit("krr", A, y, self.cfg, self.options,
                          a0=warm_start, resume_from=resume_from)
        self.A_, self.y_, self.alpha_ = A, y, result.alpha
        self.op_ = op
        self.result_ = result
        self._predictor = None
        return result

    def fit_path(self, A, y, lams):
        """Warm-started solve ladder over a lambda grid
        (``repro.tune.path.reg_path``, DESIGN.md §10): one shared
        representation build, each solve seeded from its neighbour.
        Returns a ``PathResult``; the estimator is left fitted at the
        ladder's final (smallest-lambda, least-regularized) member."""
        from repro.tune.path import reg_path
        path = reg_path(A, y, lams=lams, cfg=self.cfg,
                        options=self.options)
        last = path.results[-1]
        self.cfg = dataclasses.replace(self.cfg,
                                       lam=float(path.values[-1]))
        self.A_, self.y_, self.alpha_ = A, y, last.alpha
        self.op_ = path.op
        self.result_ = last
        self._predictor = None
        return path

    def predict(self, A_test):
        A_test = validate_queries(self.op_, A_test, name="A_test")
        _check_finite(A_test, "A_test")
        if self._predictor is None:
            self._predictor = BatchedPredictor(
                self.op_, self.alpha_, batch=self.predict_batch,
                scale=1.0 / self.cfg.lam)
        return self._predictor(A_test)

    def save(self, directory: str) -> str:
        """Persist the fitted model as a serving artifact
        (``repro.serve.artifacts.save_model``, DESIGN.md §13): restore
        with ``repro.serve.load_model`` / ``ModelRegistry.load`` — no
        refit, no live estimator needed.  Returns the artifact path."""
        from repro.serve.artifacts import save_model
        return save_model(directory, self)
