"""s-Step Dual Coordinate Descent (paper Algorithm 2) for kernel SVM.

Mathematically equivalent to ``dcd.dcd_ksvm`` (same coordinate schedule =>
same iterates in exact arithmetic), but computes the kernel data for ``s``
future coordinates up front:

    G_k = K(Atil_k, Atil_k) + omega*I in R^{s x s}  -- all cross terms the
                                                       inner recurrence needs
    U_k^T alpha in R^s                              -- one fused KMV

then runs the ``s`` scalar sub-problem solves sequentially with gradient
corrections (paper lines 14-23), touching only O(s^2) data and **no
communication**.

Slab-free by default (DESIGN.md §2): the ``m x s`` slab ``U_k`` is only
ever consumed through ``U_k^T alpha`` and its sampled ``s x s`` cross
block, so the solver reads both through a ``GramOperator`` and the slab
never exists in HBM.  Pass ``gram_fn`` (e.g. ``core.kernels.gram_slab`` or
the Pallas fused gram kernel) to force the legacy materialized-slab path —
kept as the parity oracle and the paper-faithful baseline.

Ragged schedules are fine: ``H % s != 0`` runs a final short round via the
pad-and-mask round protocol (``loop.pad_rounds``); padded slots produce
exactly-zero updates, so the iterates still match classical DCD.

Prefer the ``repro.api`` facade (``KernelSVM`` with
``SolverOptions(method="sstep", s=...)``) over calling this entrypoint
directly — it adds tolerance-based stopping, layout dispatch, and
prediction on top of the same round protocol (DESIGN.md §8).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs.scopes import CROSS_BLOCK, KMV, RECURRENCE, SCATTER, scoped

from .dcd import SVMConfig
from .kernels import ExactGramOperator
from .loop import pad_rounds, run_rounds


@scoped(RECURRENCE)
def sstep_dcd_inner(G0, u_dot_alpha, alpha_at, idx_s, nu, omega, s,
                    valid=None):
    """The redundant local phase shared by the serial and 2D-distributed
    solvers: ``s`` sequential scalar solves with gradient corrections
    (paper Alg. 2 lines 14-23).

    G0: (s, s) sampled cross block, u_dot_alpha: (s,), alpha_at: (s,),
    idx_s: (s,) the round's coordinates, valid: (s,) 1/0 mask for the
    ragged final round (padded slots get theta = 0).  Returns thetas (s,).
    """
    dtype = alpha_at.dtype
    ones = jnp.ones((s,), dtype) if valid is None else valid.astype(dtype)
    # same[t, j] = 1 iff i_{sk+t} == i_{sk+j} (for the omega & rho terms)
    same = (idx_s[:, None] == idx_s[None, :]).astype(dtype)
    eta = jnp.diagonal(G0) + omega               # (s,)

    def inner(j, thetas):
        tmask = (jnp.arange(s) < j).astype(dtype)    # t < j
        prior = thetas * tmask
        rho = alpha_at[j] + prior @ same[:, j]
        g = (u_dot_alpha[j] - 1.0 + omega * alpha_at[j]
             + prior @ G0[:, j]
             + omega * (prior @ same[:, j]))
        cand = jnp.clip(rho - g, 0.0, nu) - rho
        theta = jnp.where(
            jnp.abs(cand) != 0.0,
            jnp.clip(rho - g / eta[j], 0.0, nu) - rho,
            0.0,
        )
        return thetas.at[j].set(theta * ones[j])

    return jax.lax.fori_loop(0, s, inner, jnp.zeros((s,), dtype))


def make_sstep_dcd_round_fn(A: jnp.ndarray, y: jnp.ndarray, cfg: SVMConfig,
                            s: int,
                            gram_fn: Optional[Callable] = None,
                            op_factory: Optional[Callable] = None,
                            op=None, C=None, guard: bool = False,
                            ) -> Callable:
    """``round_fn(alpha, (idx_s, valid)) -> alpha`` for ``loop.run_rounds``:
    one Algorithm-2 outer round (communication phase + s local solves).

    ``op`` injects a prebuilt, already ``diag(y)``-scaled training
    operator (``operator.scale_rows(y)``) — exact or low-rank; the
    facade builds it once per fit (DESIGN.md §9).

    ``C`` overrides ``cfg.C`` with a TRACEABLE value — the batched cfg
    leaf of the fleet solver (repro.tune): vmapping the closure over
    per-member C's solves a whole C-grid in lockstep on ONE shared
    operator (DESIGN.md §10).

    ``guard=True`` switches to the guarded-carry protocol
    (``round_fn((alpha, f), xs) -> (alpha, f)`` with ``f = Ktil @
    alpha`` maintained by the residual recurrence ``f += Ktil[:, idx_s]
    @ thetas`` — the same m x s column block the fused KMV already
    evaluates, so per-round kernel work is unchanged; DESIGN.md §12).
    ``U^T alpha`` becomes the free gather ``f[idx_s]`` and drift
    correction can splice an exactly recomputed ``f`` back in (residual
    replacement, Devarakonda et al. 2016).  Requires the operator path.
    """
    if sum(x is not None for x in (gram_fn, op_factory, op)) > 1:
        raise ValueError("pass at most one of gram_fn (materialized "
                         "slab), op_factory, or op (prebuilt operator)")
    if guard and gram_fn is not None:
        raise ValueError("guard=True requires the GramOperator path "
                         "(gram_fn= is the legacy materialized oracle)")
    from .dcd import _nu_omega
    Atil = y[:, None] * A
    nu, omega = _nu_omega(cfg, C)
    if op is None and gram_fn is None:
        op = (op_factory or ExactGramOperator)(Atil, cfg.kernel)

    if guard:
        def round_fn(carry, xs):
            alpha, f = carry                     # f = Ktil @ alpha, (m,)
            idx_s, valid = xs
            G0 = op.cross_block(idx_s)           # (s, s)
            u_dot_alpha = f[idx_s]               # U^T alpha, free gather
            thetas = sstep_dcd_inner(G0, u_dot_alpha, alpha[idx_s],
                                     idx_s, nu, omega, s, valid)
            df = op.apply_at(idx_s, thetas)      # K[:, idx_s] @ thetas
            with jax.named_scope(SCATTER):
                return alpha.at[idx_s].add(thetas), f + df

        return round_fn

    def round_fn(alpha, xs):
        idx_s, valid = xs
        # --- communication phase: one fused round, one (would-be) psum ---
        if gram_fn is not None:                  # materialized m x s slab
            with jax.named_scope(KMV):
                U = gram_fn(Atil, Atil[idx_s], cfg.kernel)
                u_dot_alpha = U.T @ alpha        # (s,)
            with jax.named_scope(CROSS_BLOCK):
                G0 = U[idx_s, :]                 # V_k^T U_k, (s, s)
        else:                                    # slab-free operator path
            G0, u_dot_alpha = op.round_data(idx_s, alpha)

        # --- redundant local phase: s sequential scalar solves ----------
        thetas = sstep_dcd_inner(G0, u_dot_alpha, alpha[idx_s], idx_s,
                                 nu, omega, s, valid)
        with jax.named_scope(SCATTER):
            return alpha.at[idx_s].add(thetas)   # alpha_{sk+s}

    return round_fn


# repro: noqa[CHK-STATIC] gram_fn/op_factory are module-level functions
#   (or None) at every call site; passing a fresh closure retraces by
#   design — it is the documented parity-oracle escape hatch.
@partial(jax.jit, static_argnames=("cfg", "s", "record_rounds", "gram_fn",
                                   "op_factory"))
def sstep_dcd_ksvm(A: jnp.ndarray, y: jnp.ndarray, alpha0: jnp.ndarray,
                   schedule: jnp.ndarray, cfg: SVMConfig, s: int,
                   record_rounds: bool = False,
                   gram_fn: Optional[Callable] = None,
                   op_factory: Optional[Callable] = None,
                   op=None,
                   ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Run Algorithm 2 over ``ceil(H/s)`` rounds (ragged tails allowed).

    ``op_factory(Atil, kernel_cfg)`` overrides the slab-free GramOperator
    (e.g. with the Pallas KMV backend from ``repro.kernels.ops`` or the
    all-reduce operator from ``core.distributed``).  ``gram_fn(Atil, rows,
    kernel_cfg)`` instead selects the materialized-slab path.  ``op``
    (a pytree — crosses the jit boundary as data) injects a prebuilt,
    already row-scaled training operator; see ``make_sstep_dcd_round_fn``.
    """
    round_fn = make_sstep_dcd_round_fn(A, y, cfg, s, gram_fn=gram_fn,
                                       op_factory=op_factory, op=op)
    xs = pad_rounds(schedule, s)
    res = run_rounds(round_fn, alpha0, xs, record_state=record_rounds)
    return res.state, (res.state_hist if record_rounds else None)
