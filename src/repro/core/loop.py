"""Shared round-protocol loop for every solver variant (DESIGN.md §8).

All eight solver variants — {DCD, s-step DCD, BDCD, s-step BDCD} x
{serial, shard_map} — share the same outer structure: a state pytree
(alpha), a per-round transition ``round_fn(state, xs_k) -> state``, and a
schedule of per-round data ``xs``.  ``run_rounds`` is the single driver:

  * fast path (``metric_fn=None``): one ``lax.scan`` — bit-compatible
    with the legacy hand-written loops, optionally stacking per-round
    states for the convergence benchmarks;
  * tolerance path (``metric_fn`` given): one ``lax.while_loop`` that
    evaluates ``metric_fn(state)`` every ``check_every`` rounds (and at
    the final round), records it into a fixed-size history buffer, and
    stops as soon as the metric falls to ``tol``.

``run_rounds_fleet`` is the multi-problem twin (repro.tune, DESIGN.md
§10): the state carries a leading fleet axis F, the metric is
per-member, and the while-loop path maintains a vmap-safe per-member
``done`` mask — converged members are frozen in place and the loop only
exits when all F members are done.

``pad_rounds`` removes the old ``H % s == 0`` restriction: the schedule
is padded to a whole number of s-step rounds and a per-slot validity
mask rides along, so the final short round computes masked (zero)
updates for the padded slots — the iterates match the classical solver
at every ragged H (tests/test_api.py::TestRaggedTail).

The round_fns driven here are representation-agnostic: they read kernel
data only through a ``GramOperator`` (exact, low-rank, or a distributed
all-reduce operator — DESIGN.md §9), injected per fit via the
factories' ``op``/``op_factory`` parameters.

``run_rounds`` optionally threads a GUARD through the protocol
(repro.resilience, DESIGN.md §12): a ``GuardSpec`` adds (a) a jit-safe
per-round health check — a round producing a non-finite carry is
DISCARDED (the pre-round state is kept, done-mask style) and the loop
freezes with ``diverged_round``/``diverged_kind`` stamped for the host
to act on (escalation ladder in ``repro.api``); (b) periodic residual
replacement — every ``correct_every`` rounds ``correct_fn`` recomputes
the carried recurrence exactly and the observed drift is recorded into
a fixed-size buffer; (c) metric blow-up detection against the best
value seen so far.  ``guard=None`` is bit-compatible with the
pre-guard driver.

The tolerance-check and drift-correction branches run under the
``metric_check`` and ``drift_correction`` named scopes (repro.obs,
DESIGN.md §15): op metadata that names them in a device trace.

Everything here is pure ``lax``; the driver runs identically inside
``jax.jit`` and inside ``shard_map`` bodies (core/distributed.py).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.obs.scopes import DRIFT_CORRECTION, METRIC_CHECK

NO_TOL = float("-inf")        # sentinel: record the metric, never stop early

# LoopResult.diverged_kind codes (0 = healthy throughout)
DIVERGED_NONE = 0
DIVERGED_NONFINITE = 1        # round_fn produced a non-finite carry leaf
DIVERGED_METRIC = 2           # metric went non-finite or blew up vs best


class GuardSpec(NamedTuple):
    """Guard hooks for ``run_rounds`` (repro.resilience, DESIGN.md §12).

    health_fn:      state -> scalar bool, True = healthy.  Runs on the
                    FULL post-round carry every round; an unhealthy
                    round is discarded and the loop freezes.  Must cover
                    every carry leaf (``repro.analysis`` CHK-CARRY
                    pokes NaNs into each leaf to verify it does).
    correct_fn:     state -> (corrected_state, drift) — residual
                    replacement: recompute the carried recurrence
                    exactly and report the observed relative drift.
    correct_every:  cadence of ``correct_fn`` in rounds (0 = never).
    metric_blowup:  freeze when a checked metric exceeds
                    ``metric_blowup * best_so_far`` (inf disables).
    """

    health_fn: Callable
    correct_fn: Optional[Callable] = None
    correct_every: int = 0
    metric_blowup: float = 1e4


class LoopResult(NamedTuple):
    """Output of ``run_rounds`` (a pytree, so it can cross a jit boundary).

    state:       final solver state (alpha).
    state_hist:  per-round stacked states (scan mode + record_state) or None.
    metric_hist: (n_check_slots,) metric values (while mode; only the
                 first ``checks_run`` slots were evaluated — slice with
                 it, values may legitimately be inf/nan) or None (scan).
    checks_run:  number of metric evaluations actually performed.
    rounds_run:  number of rounds actually executed.
    converged:   metric <= tol at some check point (``run_rounds_fleet``:
                 the (F,) per-member mask; metric_hist is (n_checks, F)).

    Guard extras (``guard=`` runs only; None otherwise — trailing
    defaults keep every pre-guard construction site valid):

    drift_hist:     (n_corrections,) observed relative drift at each
                    residual replacement (only the first ``corrections``
                    slots were evaluated).
    corrections:    number of drift corrections performed.
    diverged_round: 0-based index of the first unhealthy round, or -1.
                    On non-finite divergence ``state`` is the LAST GOOD
                    (pre-round) carry; the unhealthy update was never
                    applied.
    diverged_kind:  DIVERGED_NONE / DIVERGED_NONFINITE / DIVERGED_METRIC.
    """

    state: Any
    state_hist: Optional[Any]
    metric_hist: Optional[jnp.ndarray]
    checks_run: jnp.ndarray
    rounds_run: jnp.ndarray
    converged: jnp.ndarray
    drift_hist: Optional[jnp.ndarray] = None
    corrections: Optional[jnp.ndarray] = None
    diverged_round: Optional[jnp.ndarray] = None
    diverged_kind: Optional[jnp.ndarray] = None

    def metric_history(self) -> Optional[jnp.ndarray]:
        """The evaluated prefix ``metric_hist[:checks_run]``.

        HOST-SYNC: ``int(self.checks_run)`` blocks on the device value
        — calling this inside a traced function raises (it is a result
        accessor, not loop code), and calling it on a freshly returned
        result synchronizes the dispatch stream.  ``None`` when no
        metric was recorded (scan mode).  Edge cases: ``checks_run ==
        0`` (e.g. an empty schedule — the while loop never ran) returns
        the empty ``(0,)`` slice, not None; fleet results
        (``run_rounds_fleet``) slice the same way with the check axis
        leading — shape ``(checks_run, F)``."""
        if self.metric_hist is None:
            return None
        return self.metric_hist[:int(self.checks_run)]

    def drift_history(self) -> Optional[jnp.ndarray]:
        """The evaluated drift prefix ``drift_hist[:corrections]``.

        HOST-SYNC like ``metric_history`` (``int(self.corrections)``
        blocks).  ``None`` when the run was unguarded or had no
        residual-replacement cadence (``correct_every == 0`` — the
        guard then records no drift buffer at all); a guarded run whose
        cadence never fired returns the empty ``(0,)`` slice."""
        if self.drift_hist is None:
            return None
        return self.drift_hist[:int(self.corrections)]


def pad_rounds(schedule: jnp.ndarray, s: int):
    """Reshape an (H, ...) schedule into ((R, s, ...), (R, s)) rounds +
    validity mask with R = ceil(H/s); padded slots carry index 0 and
    valid 0.0, so masked round_fns make them exact no-ops."""
    H = schedule.shape[0]
    R = -(-H // s)
    pad = R * s - H
    if pad:
        schedule = jnp.concatenate(
            [schedule, jnp.zeros((pad,) + schedule.shape[1:],
                                 schedule.dtype)], axis=0)
    valid = (jnp.arange(R * s) < H).astype(jnp.float32)
    return (schedule.reshape((R, s) + schedule.shape[1:]),
            valid.reshape(R, s))


def run_rounds(round_fn: Callable, state0: Any, xs: Any, *,
               tol: float = NO_TOL, check_every: int = 1,
               metric_fn: Optional[Callable] = None,
               record_state: bool = False,
               guard: Optional[GuardSpec] = None) -> LoopResult:
    """Drive ``R = len(xs)`` rounds of ``round_fn`` (see module docstring).

    xs is a pytree of arrays with a shared leading round axis.  With
    ``metric_fn=None`` this is exactly the legacy ``lax.scan`` loop;
    otherwise a ``lax.while_loop`` with early stopping at ``tol``
    (pass ``tol=NO_TOL`` to record the metric without ever stopping).
    ``guard`` switches to the guarded while-loop driver (module
    docstring; works with or without a metric).
    """
    R = jax.tree_util.tree_leaves(xs)[0].shape[0]

    if guard is not None:
        if record_state:
            raise ValueError("guard= and record_state= are mutually "
                             "exclusive (guarded runs use the while-loop "
                             "driver, which stacks no per-round states)")
        return _run_rounds_guarded(round_fn, state0, xs, R, tol=tol,
                                   check_every=check_every,
                                   metric_fn=metric_fn, guard=guard)

    if metric_fn is None:
        def body(state, x):
            new = round_fn(state, x)
            return new, (new if record_state else 0.0)

        state, ys = jax.lax.scan(body, state0, xs)
        return LoopResult(state, ys if record_state else None, None,
                          jnp.asarray(0), jnp.asarray(R),
                          jnp.asarray(False))

    if check_every < 1:
        raise ValueError(f"check_every must be >= 1, got {check_every}")
    n_checks = -(-R // check_every)
    mdtype = jax.eval_shape(metric_fn, state0).dtype
    hist0 = jnp.full((n_checks,), jnp.inf, mdtype)
    tol_v = jnp.asarray(tol, mdtype)

    def cond(carry):
        k, _, _, _, conv = carry
        return (k < R) & jnp.logical_not(conv)

    def body(carry):
        k, state, hist, nchk, _ = carry
        x = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, k, 0, keepdims=False),
            xs)
        state = round_fn(state, x)
        do_check = ((k + 1) % check_every == 0) | (k + 1 == R)

        def check(args):
            st, h, n = args
            with jax.named_scope(METRIC_CHECK):
                v = metric_fn(st)
            return h.at[n].set(v), n + 1, v <= tol_v

        def skip(args):
            return args[1], args[2], jnp.asarray(False)

        hist, nchk, conv = jax.lax.cond(do_check, check, skip,
                                        (state, hist, nchk))
        return k + 1, state, hist, nchk, conv

    k, state, hist, nchk, conv = jax.lax.while_loop(
        cond, body, (jnp.asarray(0), state0, hist0, jnp.asarray(0),
                     jnp.asarray(False)))
    return LoopResult(state, None, hist, nchk, k, conv)


def _run_rounds_guarded(round_fn: Callable, state0: Any, xs: Any, R: int,
                        *, tol: float, check_every: int,
                        metric_fn: Optional[Callable],
                        guard: GuardSpec) -> LoopResult:
    """The guarded while-loop driver behind ``run_rounds(guard=...)``.

    Divergence handling follows the fleet freeze idiom: the unhealthy
    round's update is DISCARDED (``jnp.where`` keeps the pre-round
    carry), the first bad round index and kind are stamped, and the
    loop condition exits — the host (repro.api's escalation ladder)
    decides what to run next from the last good state.
    """
    if check_every < 1:
        raise ValueError(f"check_every must be >= 1, got {check_every}")
    has_metric = metric_fn is not None
    n_checks = -(-R // check_every) if has_metric else 1
    if has_metric:
        mdtype = jax.eval_shape(metric_fn, state0).dtype
    else:
        mdtype = jnp.asarray(0.0).dtype
    hist0 = jnp.full((n_checks,), jnp.inf, mdtype)
    tol_v = jnp.asarray(tol, mdtype)
    blowup = jnp.asarray(guard.metric_blowup, mdtype)

    has_corr = (guard.correct_fn is not None and guard.correct_every >= 1)
    n_corr = -(-R // guard.correct_every) if has_corr else 1
    if has_corr:
        ddtype = jax.eval_shape(guard.correct_fn, state0)[1].dtype
    else:
        ddtype = mdtype
    drift0 = jnp.zeros((n_corr,), ddtype)

    def cond(carry):
        k, _, _, _, conv, _, _, _, div, _ = carry
        return (k < R) & jnp.logical_not(conv) & (div < 0)

    def body(carry):
        k, state, hist, nchk, _, best, dhist, ncorr, div, kind = carry
        x = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, k, 0, keepdims=False),
            xs)
        new = round_fn(state, x)
        ok = guard.health_fn(new)
        # freeze idiom: an unhealthy update is discarded wholesale —
        # the carry the host resumes from is the last good state
        state = jax.tree_util.tree_map(
            lambda nw, old: jnp.where(ok, nw, old), new, state)
        div = jnp.where(ok, div, k)
        kind = jnp.where(ok, kind, DIVERGED_NONFINITE)

        if has_corr:
            do_corr = ok & ((k + 1) % guard.correct_every == 0)

            def correct(args):
                st, dh, nc = args
                with jax.named_scope(DRIFT_CORRECTION):
                    st2, drift = guard.correct_fn(st)
                return st2, dh.at[nc].set(drift), nc + 1

            state, dhist, ncorr = jax.lax.cond(
                do_corr, correct, lambda args: args, (state, dhist, ncorr))

        conv = jnp.asarray(False)
        if has_metric:
            do_check = ok & (((k + 1) % check_every == 0) | (k + 1 == R))

            def check(args):
                st, h, n = args
                with jax.named_scope(METRIC_CHECK):
                    v = metric_fn(st)
                finite = jnp.isfinite(v)
                blown = jnp.isfinite(best) & (v > blowup * best)
                return (h.at[n].set(v), n + 1, finite & (v <= tol_v),
                        jnp.logical_not(finite) | blown,
                        jnp.where(finite, jnp.minimum(best, v), best))

            def skip(args):
                return (args[1], args[2], jnp.asarray(False),
                        jnp.asarray(False), best)

            hist, nchk, conv, bad, best = jax.lax.cond(
                do_check, check, skip, (state, hist, nchk))
            div = jnp.where(bad & (div < 0), k, div)
            kind = jnp.where(bad & (kind == DIVERGED_NONE),
                             DIVERGED_METRIC, kind)

        return (k + 1, state, hist, nchk, conv, best, dhist, ncorr, div,
                kind)

    init = (jnp.asarray(0), state0, hist0, jnp.asarray(0),
            jnp.asarray(False), jnp.asarray(jnp.inf, mdtype), drift0,
            jnp.asarray(0), jnp.asarray(-1), jnp.asarray(DIVERGED_NONE))
    (k, state, hist, nchk, conv, _, dhist, ncorr, div,
     kind) = jax.lax.while_loop(cond, body, init)
    return LoopResult(state, None, hist if has_metric else None, nchk, k,
                      conv, dhist if has_corr else None,
                      ncorr if has_corr else None, div, kind)


def run_rounds_fleet(round_fn: Callable, state0: Any, xs: Any, *,
                     tol: float = NO_TOL, check_every: int = 1,
                     metric_fn: Optional[Callable] = None) -> LoopResult:
    """Fleet variant of ``run_rounds``: one round protocol driving F
    independent problems in lockstep (repro.tune, DESIGN.md §10).

    ``state0`` is a pytree whose leaves carry a leading fleet axis F
    (e.g. alpha: (F, m)); ``round_fn(state, xs_k) -> state`` advances
    every member at once (typically a ``jax.vmap``-ed per-member round —
    leaves of the shared operator stay unbatched, so the gram work is
    computed ONCE per round for the whole fleet).  ``xs`` is shared
    across members (one schedule, F problems).

    ``metric_fn(state) -> (F,)`` gives per-member convergence values.
    The while-loop path keeps a per-member ``done`` mask: members at or
    below ``tol`` are FROZEN — subsequent rounds compute their update in
    lockstep but ``jnp.where`` discards it, so a converged member's
    state never drifts — and the loop exits once every member is done
    (vmap-safe masking: no data-dependent shapes, no per-member early
    exit).  ``metric_hist`` is ``(n_checks, F)``; ``converged`` is the
    final ``(F,)`` mask.

    The scan path (``metric_fn=None``) is the plain lockstep schedule —
    bit-comparable with F independent ``run_rounds`` scans.
    """
    R = jax.tree_util.tree_leaves(xs)[0].shape[0]

    if metric_fn is None:
        def body(state, x):
            return round_fn(state, x), 0.0

        state, _ = jax.lax.scan(body, state0, xs)
        F = jax.tree_util.tree_leaves(state0)[0].shape[0]
        return LoopResult(state, None, None, jnp.asarray(0),
                          jnp.asarray(R), jnp.zeros((F,), bool))

    if check_every < 1:
        raise ValueError(f"check_every must be >= 1, got {check_every}")
    n_checks = -(-R // check_every)
    mshape = jax.eval_shape(metric_fn, state0)
    F = mshape.shape[0]
    hist0 = jnp.full((n_checks, F), jnp.inf, mshape.dtype)
    tol_v = jnp.asarray(tol, mshape.dtype)

    def freeze(done, old, new):
        """Per-member where over a leading-F pytree leaf."""
        def leaf(o, nw):
            return jnp.where(done.reshape((F,) + (1,) * (nw.ndim - 1)),
                             o, nw)
        return jax.tree_util.tree_map(leaf, old, new)

    def cond(carry):
        k, _, _, _, done = carry
        return (k < R) & jnp.logical_not(jnp.all(done))

    def body(carry):
        k, state, hist, nchk, done = carry
        x = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, k, 0, keepdims=False),
            xs)
        state = freeze(done, state, round_fn(state, x))
        do_check = ((k + 1) % check_every == 0) | (k + 1 == R)

        def check(args):
            st, h, n, d = args
            with jax.named_scope(METRIC_CHECK):
                v = metric_fn(st)                    # (F,)
            return h.at[n].set(v), n + 1, d | (v <= tol_v)

        def skip(args):
            return args[1], args[2], args[3]

        hist, nchk, done = jax.lax.cond(do_check, check, skip,
                                        (state, hist, nchk, done))
        return k + 1, state, hist, nchk, done

    k, state, hist, nchk, done = jax.lax.while_loop(
        cond, body, (jnp.asarray(0), state0, hist0, jnp.asarray(0),
                     jnp.zeros((F,), bool)))
    return LoopResult(state, None, hist, nchk, k, done)
