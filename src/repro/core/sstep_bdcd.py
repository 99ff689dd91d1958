"""s-Step Block Dual Coordinate Descent (paper Algorithm 4) for K-RR.

One outer round gathers everything ``s`` exact b x b block solves need:

    Gblk    = K(A_Omega, A_Omega)  in R^{sb x sb}   (sampled cross block)
    Q^T alpha in R^{sb}                             (one fused KMV)

with a single collective, then repairs the deferred alpha update with the
correction sums of paper eq. (3):

    dalpha_{sk+j} = G^{-1}( V_j^T y - m V_j^T alpha_sk
                            - m     sum_{t<j} V_j^T V_t dalpha_t
                            - 1/lam U_j^T alpha_sk
                            - 1/lam sum_{t<j} U_j^T V_t dalpha_t )

All correction data lives in the (sb x sb) ``Gblk`` and the
index-collision mask — O((sb)^2) redundant flops, zero communication.

The s systems ``G = m I + K_jj / lam`` are ``Gblk``'s diagonal b x b
blocks, all known before the first step: only the right-hand sides wait
on earlier steps.  The round factors them once, batched (Cholesky, and
the inverses from the factors), under the ``block_solve`` scope, and each
step applies its block's inverse in float32.  ``K_jj`` is positive
semidefinite, so each ``G`` is symmetric positive definite with every
eigenvalue >= m and cond <= 1 + b max K / (lam m): the factorization
exists, and factoring up front gives the per-step solve's answer to
rounding.

Slab-free by default (DESIGN.md §2): the ``m x sb`` slab ``Q_k`` is only
consumed through ``Q^T alpha`` and ``Gblk``, both exposed by
``GramOperator`` without materializing ``Q_k``.  ``gram_fn`` forces the
legacy materialized-slab path (parity oracle / paper-faithful baseline).

Ragged schedules are fine: ``H % s != 0`` runs a final short round via the
pad-and-mask round protocol (``loop.pad_rounds``); padded blocks produce
exactly-zero updates, so the iterates still match classical BDCD.

Prefer the ``repro.api`` facade (``KernelRidge`` with
``SolverOptions(method="sstep", s=..., b=...)``) over calling this
entrypoint directly — it adds tolerance-based stopping, layout dispatch,
and prediction on top of the same round protocol (DESIGN.md §8).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.scipy.linalg import cho_solve

from repro.obs.scopes import (BLOCK_SOLVE, CROSS_BLOCK, KMV, RECURRENCE,
                              SCATTER, scoped)

from .bdcd import KRRConfig
from .kernels import ExactGramOperator
from .loop import pad_rounds, run_rounds


@scoped(RECURRENCE)
def sstep_bdcd_inner(Gblk, QTalpha, alpha_at, y_at, flat, m, inv_lam,
                     s, b, valid=None):
    """The redundant local phase shared by the serial and 2D-distributed
    solvers: ``s`` sequential b x b solves with eq. (3) corrections.

    The s systems are factored once, batched, before the loop (module
    docstring); step j applies ``G_j^{-1}`` to its right-hand side.

    Gblk: (sb, sb), QTalpha: (sb,), alpha_at/y_at: (s, b), flat: (sb,),
    valid: (s,) 1/0 mask for the ragged final round (padded blocks get
    dalpha = 0).  Returns dalpha: (s, b).
    """
    dtype = alpha_at.dtype
    ones = jnp.ones((s,), dtype) if valid is None else valid.astype(dtype)
    # collide[t, q, j, p] = 1 iff flat[t*b+q] == flat[j*b+p]
    collide = (flat[:, None] == flat[None, :]).astype(dtype)
    collide4 = collide.reshape(s, b, s, b)
    Gblk4 = Gblk.reshape(s, b, s, b)                  # [t, q, j, p]
    eye_b = jnp.eye(b, dtype=dtype)

    with jax.named_scope(BLOCK_SOLVE):
        # G_j = m I + K_jj / lam: SPD, every eigenvalue >= m
        G = inv_lam * jnp.einsum("jpjq->jpq", Gblk4) + m * eye_b
        Ginv = cho_solve(
            (jnp.linalg.cholesky(G), True), jnp.broadcast_to(eye_b, G.shape))

    def inner(j, dalpha):                             # dalpha: (s, b)
        tmask = (jnp.arange(s) < j).astype(dtype)
        prior = dalpha * tmask[:, None]               # zero for t >= j
        # m * sum_t V_j^T V_t dalpha_t    -> (b,)
        vv = jnp.einsum("tq,tqp->p", prior, collide4[:, :, j, :])
        # 1/lam * sum_t U_j^T V_t dalpha_t = Q[idx_t, jb:jb+b]^T dalpha_t
        uv = jnp.einsum("tq,tqp->p", prior, Gblk4[:, :, j, :])
        rhs = (y_at[j] - m * alpha_at[j] - m * vv
               - inv_lam * jax.lax.dynamic_slice_in_dim(QTalpha, j * b, b)
               - inv_lam * uv)
        with jax.named_scope(BLOCK_SOLVE):
            # multiply-and-sum: float32 on every backend, unlike a dot
            sol = jnp.sum(Ginv[j] * rhs, axis=1)
        return dalpha.at[j].set(sol * ones[j])

    return jax.lax.fori_loop(0, s, inner, jnp.zeros((s, b), dtype))


def make_sstep_bdcd_round_fn(A: jnp.ndarray, y: jnp.ndarray, cfg: KRRConfig,
                             s: int,
                             gram_fn: Optional[Callable] = None,
                             op_factory: Optional[Callable] = None,
                             op=None, lam=None, guard: bool = False,
                             ) -> Callable:
    """``round_fn(alpha, (idx, valid)) -> alpha`` for ``loop.run_rounds``:
    one Algorithm-4 outer round; idx: (s, b), valid: (s,).  ``op``
    injects a prebuilt operator (exact or low-rank) over the training
    representation; the facade builds it once per fit (DESIGN.md §9).

    ``lam`` overrides ``cfg.lam`` with a TRACEABLE value — the batched
    cfg leaf of the fleet solver (repro.tune): vmapping the closure over
    per-member lam solves a whole regularization grid in lockstep on ONE
    shared operator (DESIGN.md §10).

    ``guard=True`` switches to the guarded-carry protocol
    (``round_fn((alpha, f), xs) -> (alpha, f)`` with ``f = K @ alpha``
    maintained by ``f += K[:, flat] @ dalpha`` — the same m x sb block
    the fused KMV already evaluates; ``Q^T alpha`` becomes the free
    gather ``f[flat]``, and drift correction splices an exactly
    recomputed ``f`` back in — residual replacement for the s-step
    recurrence; DESIGN.md §12).  Requires the operator path."""
    if sum(x is not None for x in (gram_fn, op_factory, op)) > 1:
        raise ValueError("pass at most one of gram_fn (materialized "
                         "slab), op_factory, or op (prebuilt operator)")
    if guard and gram_fn is not None:
        raise ValueError("guard=True requires the GramOperator path "
                         "(gram_fn= is the legacy materialized oracle)")
    m = A.shape[0]
    inv_lam = 1.0 / (cfg.lam if lam is None else lam)
    if op is None and gram_fn is None:
        op = (op_factory or ExactGramOperator)(A, cfg.kernel)
    if op is not None:
        op = op.for_rounds()            # once per solve, outside the loop

    if guard:
        def round_fn(carry, xs):
            alpha, f = carry                   # f = K @ alpha, (m,)
            idx, valid = xs                    # idx: (s, b)
            b = idx.shape[1]
            flat = idx.reshape(s * b)
            Gblk = op.cross_block(flat)        # (sb, sb)
            QTalpha = f[flat]                  # Q^T alpha, free gather
            dalpha = sstep_bdcd_inner(Gblk, QTalpha, alpha[idx], y[idx],
                                      flat, m, inv_lam, s, b, valid)
            d = dalpha.reshape(s * b)
            # duplicate coordinates in ``flat`` accumulate identically
            # in .at[].add and in the K[:, flat] @ d contraction
            df = op.apply_at(flat, d)
            with jax.named_scope(SCATTER):
                return alpha.at[flat].add(d), f + df

        return round_fn

    def round_fn(alpha, xs):
        idx, valid = xs                        # idx: (s, b)
        b = idx.shape[1]
        flat = idx.reshape(s * b)
        # --- communication phase ----------------------------------------
        if gram_fn is not None:                # materialized m x sb slab
            with jax.named_scope(KMV):
                Q = gram_fn(A, A[flat], cfg.kernel)
                QTalpha = Q.T @ alpha          # (s*b,)
            with jax.named_scope(CROSS_BLOCK):
                Gblk = Q[flat, :]              # (s*b, s*b)
        else:                                  # slab-free operator path
            Gblk, QTalpha = op.round_data(flat, alpha)
        y_at = y[idx]                          # (s, b)
        alpha_at = alpha[idx]                  # (s, b)

        # --- redundant local phase: s block solves -----------------------
        dalpha = sstep_bdcd_inner(Gblk, QTalpha, alpha_at, y_at, flat,
                                  m, inv_lam, s, b, valid)
        with jax.named_scope(SCATTER):
            return alpha.at[flat].add(dalpha.reshape(s * b))

    return round_fn


# repro: noqa[CHK-STATIC] gram_fn/op_factory are module-level functions
#   (or None) at every call site; passing a fresh closure retraces by
#   design — it is the documented parity-oracle escape hatch.
@partial(jax.jit, static_argnames=("cfg", "s", "record_rounds", "gram_fn",
                                   "op_factory"))
def sstep_bdcd_krr(A: jnp.ndarray, y: jnp.ndarray, alpha0: jnp.ndarray,
                   schedule: jnp.ndarray, cfg: KRRConfig, s: int,
                   record_rounds: bool = False,
                   gram_fn: Optional[Callable] = None,
                   op_factory: Optional[Callable] = None,
                   op=None,
                   ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Run Algorithm 4.  ``schedule`` is the (H, b) block schedule from
    ``bdcd.block_schedule``; ragged H (H % s != 0) runs a masked final
    short round.  ``op`` (a pytree — crosses the jit boundary as data)
    injects a prebuilt operator; see ``make_sstep_bdcd_round_fn``."""
    round_fn = make_sstep_bdcd_round_fn(A, y, cfg, s, gram_fn=gram_fn,
                                        op_factory=op_factory, op=op)
    xs = pad_rounds(schedule, s)
    res = run_rounds(round_fn, alpha0, xs, record_state=record_rounds)
    return res.state, (res.state_hist if record_rounds else None)
