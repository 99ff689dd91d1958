"""Classical Dual Coordinate Descent (paper Algorithm 1) for kernel SVM.

Solves the Lagrangian-dual K-SVM problem

    argmin_{alpha}  1/2 sum_ij alpha_i alpha_j y_i y_j K(a_i, a_j) - sum_i alpha_i
                    (+ 1/(4C) ||alpha||^2 for the L2 / squared-hinge variant)
    s.t. 0 <= alpha_i <= C   (L1)   /   0 <= alpha_i   (L2)

one coordinate at a time.  Each iteration needs one column ``u_k = K(Atil,
a_{i_k})`` of the kernel matrix — on a distributed machine that is one
all-reduce per iteration, which is exactly the bottleneck the s-step
variant (``sstep_dcd.py``) removes.

The column is only ever consumed through ``u_k^T alpha`` and ``u_k[i_k]``
(= K(a_i, a_i)), so the default path reads both through a slab-free
``GramOperator`` (DESIGN.md §2); ``gram_fn`` forces the legacy
materialized-column path, kept as the parity oracle.

Prefer the ``repro.api`` facade (``KernelSVM`` with
``SolverOptions(method="classical")``) over calling this entrypoint
directly — it adds tolerance-based stopping, layout dispatch, and
prediction on top of the same round protocol (DESIGN.md §8).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs.scopes import CROSS_BLOCK, KMV, RECURRENCE, SCATTER, scoped

from .kernels import ExactGramOperator, KernelConfig
from .loop import run_rounds

L1 = "l1"
L2 = "l2"


@dataclasses.dataclass(frozen=True)
class SVMConfig:
    C: float = 1.0
    loss: str = L1            # "l1" (hinge) or "l2" (squared hinge)
    kernel: KernelConfig = dataclasses.field(default_factory=KernelConfig)

    def __post_init__(self):
        if self.loss not in (L1, L2):
            raise ValueError(f"loss must be 'l1' or 'l2', got {self.loss!r}")

    @property
    def nu(self) -> float:
        """Upper clip bound on alpha (paper line 2)."""
        return self.C if self.loss == L1 else jnp.inf

    @property
    def omega(self) -> float:
        """Diagonal shift (paper line 2)."""
        return 0.0 if self.loss == L1 else 1.0 / (2.0 * self.C)


def _nu_omega(cfg: SVMConfig, C=None):
    """(nu, omega) from the config, or re-derived from a traceable ``C``
    override (the fleet solvers' batched cfg leaf — see
    ``make_dcd_round_fn``)."""
    if C is None:
        return cfg.nu, cfg.omega
    if cfg.loss == L1:
        return C, 0.0
    return jnp.inf, 1.0 / (2.0 * C)


def coordinate_schedule(key: jax.Array, H: int, m: int) -> jnp.ndarray:
    """i_k ~ Uniform[m], k = 1..H.  Identical schedule is used by DCD and
    s-step DCD so that the two produce bitwise-comparable iterates."""
    return jax.random.randint(key, (H,), 0, m)


@scoped(RECURRENCE)
def _dcd_theta(alpha_i, g, eta, nu):
    """One DCD coordinate update (paper lines 8-16). Returns theta."""
    cand = jnp.clip(alpha_i - g, 0.0, nu) - alpha_i
    gtilde = jnp.abs(cand)
    return jnp.where(
        gtilde != 0.0,
        jnp.clip(alpha_i - g / eta, 0.0, nu) - alpha_i,
        0.0,
    )


def make_dcd_round_fn(A: jnp.ndarray, y: jnp.ndarray, cfg: SVMConfig,
                      gram_fn: Optional[Callable] = None,
                      op_factory: Optional[Callable] = None,
                      op=None, C=None, guard: bool = False) -> Callable:
    """``round_fn(alpha, i) -> alpha`` for ``loop.run_rounds``: one
    Algorithm-1 coordinate step.  This closure IS the classical solver;
    ``dcd_ksvm`` and the ``repro.api`` facade both drive it.

    ``op`` injects a prebuilt ``GramOperator`` over the TRAINING
    representation (already row-scaled by ``diag(y)`` — use
    ``operator.scale_rows(y)``); the facade builds it once per fit and
    reuses it for prediction (DESIGN.md §9).

    ``C`` overrides ``cfg.C`` with a TRACEABLE value — the batched cfg
    leaf of the fleet solver (repro.tune): the derived clip bound nu and
    L2 shift omega become traced scalars, so ``jax.vmap`` over
    per-member C's solves a whole C-grid in lockstep (DESIGN.md §10).

    ``guard=True`` switches to the guarded-carry protocol
    (``round_fn((alpha, f), i) -> (alpha, f)`` with ``f = Ktil @ alpha``
    maintained by the residual recurrence ``f += Ktil[:, i] * theta`` —
    one ``apply_at`` of the SAME column the round already evaluates, so
    the per-round kernel work is unchanged; DESIGN.md §12).  ``u^T
    alpha`` then becomes the free gather ``f[i]``, and drift correction
    can splice an exactly recomputed ``f`` back in (residual
    replacement).  Requires the operator path (no ``gram_fn``).
    """
    if sum(x is not None for x in (gram_fn, op_factory, op)) > 1:
        raise ValueError("pass at most one of gram_fn (materialized "
                         "slab), op_factory, or op (prebuilt operator)")
    if guard and gram_fn is not None:
        raise ValueError("guard=True requires the GramOperator path "
                         "(gram_fn= is the legacy materialized oracle)")
    Atil = y[:, None] * A                       # diag(y) @ A
    nu, omega = _nu_omega(cfg, C)
    if op is None and gram_fn is None:
        op = (op_factory or ExactGramOperator)(Atil, cfg.kernel)

    if guard:
        def round_fn(carry, i):
            alpha, f = carry                    # f = Ktil @ alpha, (m,)
            idx = i[None]
            eta = op.cross_block(idx)[0, 0] + omega
            g = f[i] - 1.0 + omega * alpha[i]   # u^T alpha = f[i], free
            theta = _dcd_theta(alpha[i], g, eta, nu)
            df = op.apply_at(idx, theta[None])
            with jax.named_scope(SCATTER):
                return alpha.at[i].add(theta), f + df

        return round_fn

    def round_fn(alpha, i):
        idx = i[None]
        if gram_fn is not None:                 # materialized m x 1 column
            with jax.named_scope(KMV):
                u = gram_fn(Atil, Atil[idx], cfg.kernel)[:, 0]
                uTa = u @ alpha
            with jax.named_scope(CROSS_BLOCK):
                eta = u[i] + omega
            g = uTa - 1.0 + omega * alpha[i]
        else:                                   # slab-free operator path
            G, uTa = op.round_data(idx, alpha)  # (1, 1), (1,)
            eta = G[0, 0] + omega
            g = uTa[0] - 1.0 + omega * alpha[i]
        theta = _dcd_theta(alpha[i], g, eta, nu)
        with jax.named_scope(SCATTER):
            return alpha.at[i].add(theta)

    return round_fn


# repro: noqa[CHK-STATIC] gram_fn/op_factory are module-level functions
#   (or None) at every call site; passing a fresh closure retraces by
#   design — it is the documented parity-oracle escape hatch.
@partial(jax.jit, static_argnames=("cfg", "record_every", "gram_fn",
                                   "op_factory"))
def dcd_ksvm(A: jnp.ndarray, y: jnp.ndarray, alpha0: jnp.ndarray,
             schedule: jnp.ndarray, cfg: SVMConfig,
             record_every: int = 0,
             gram_fn: Optional[Callable] = None,
             op_factory: Optional[Callable] = None,
             op=None,
             ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Run Algorithm 1 for ``H = len(schedule)`` iterations.

    Returns ``(alpha_H, history)`` where ``history`` stacks ``alpha`` every
    ``record_every`` iterations (or ``None`` when 0).  ``op`` (a pytree —
    it crosses the jit boundary as data) injects a prebuilt, already
    row-scaled training operator; see ``make_dcd_round_fn``.
    """
    round_fn = make_dcd_round_fn(A, y, cfg, gram_fn=gram_fn,
                                 op_factory=op_factory, op=op)
    res = run_rounds(round_fn, alpha0, schedule,
                     record_state=bool(record_every))
    if record_every:
        return res.state, res.state_hist[record_every - 1::record_every]
    return res.state, None
