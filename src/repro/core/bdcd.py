"""Block Dual Coordinate Descent (paper Algorithm 3) for kernel ridge
regression.

K-RR dual (paper eq. 2):  the optimality system is
    ((1/lambda) K + m I) alpha = y
BDCD samples a block of ``b`` coordinates per iteration, extracts the b x b
sub-system and solves it exactly:

    G_k = (1/lambda) K(A_k, A_k) + m I      (b x b)
    dalpha = G_k^{-1}(V_k^T y - m V_k^T alpha - (1/lambda) U_k^T alpha)

The ``m x b`` slab ``U_k = K(A, V_k^T A)`` only enters through
``U_k^T alpha`` and its sampled b x b block, so the default path is
slab-free via ``GramOperator`` (DESIGN.md §2); ``gram_fn`` forces the
legacy materialized-slab path (the parity oracle).

Prefer the ``repro.api`` facade (``KernelRidge`` with
``SolverOptions(method="classical", b=...)``) over calling this
entrypoint directly — it adds tolerance-based stopping, layout dispatch,
and prediction on top of the same round protocol (DESIGN.md §8).
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


@dataclasses.dataclass(frozen=True)
class KRRConfig:
    lam: float = 1.0          # ridge parameter lambda
    kernel: KernelConfig = dataclasses.field(default_factory=KernelConfig)


def block_schedule(key: jax.Array, H: int, m: int, b: int) -> jnp.ndarray:
    """(H, b) coordinate blocks, each sampled uniformly WITHOUT replacement
    (paper Alg. 3 line 4). Shared by BDCD and s-step BDCD.

    Each block is drawn by Floyd's algorithm in O(b^2) work: step j draws
    t uniform on [0, m-b+j] and keeps it, or m-b+j if t is already taken,
    which makes every b-subset equally likely.  ``jax.random.choice``
    would permute all m indices per block, an (H, m) sort that does not
    fit a chip's memory at m=2^18, H=4096."""
    if not 1 <= b <= m:
        raise ValueError(f"block size b={b} must be in [1, m={m}]")
    keys = jax.random.split(key, H)
    tops = jnp.arange(m - b, m)

    def one(k):
        ts = jax.random.randint(k, (b,), 0, tops + 1)

        def pick(j, out):
            t = ts[j]
            return out.at[j].set(jnp.where(jnp.any(out == t), tops[j], t))

        return jax.lax.fori_loop(0, b, pick, jnp.full((b,), -1, ts.dtype))

    return jax.vmap(one)(keys)


@scoped(RECURRENCE)
def _block_solve(Gblk, uTa, alpha_at, y_at, m, inv_lam):
    """The exact b x b block solve (paper Alg. 3 lines 6-7): dalpha."""
    b = Gblk.shape[0]
    G = inv_lam * Gblk + m * jnp.eye(b, dtype=Gblk.dtype)
    rhs = y_at - m * alpha_at - inv_lam * uTa
    return jnp.linalg.solve(G, rhs)


def make_bdcd_round_fn(A: jnp.ndarray, y: jnp.ndarray, cfg: KRRConfig,
                       gram_fn: Optional[Callable] = None,
                       op_factory: Optional[Callable] = None,
                       op=None, lam=None, guard: bool = False) -> Callable:
    """``round_fn(alpha, idx) -> alpha`` for ``loop.run_rounds``: one
    Algorithm-3 exact b x b block solve.  ``op`` injects a prebuilt
    ``GramOperator`` (exact or low-rank) over the training
    representation; the facade builds it once per fit (DESIGN.md §9).

    ``lam`` overrides ``cfg.lam`` with a TRACEABLE value — the batched
    cfg leaf of the fleet solver (repro.tune): ``jax.vmap`` over
    per-member scalars turns one closure into F lockstep problems
    sharing the operator (DESIGN.md §10).

    ``guard=True`` switches to the guarded-carry protocol
    (``round_fn((alpha, f), idx) -> (alpha, f)`` with ``f = K @ alpha``
    maintained by ``f += K[:, idx] @ dalpha`` — the same m x b block
    the round already evaluates; ``U^T alpha`` becomes the free gather
    ``f[idx]``, and drift correction splices an exactly recomputed
    ``f`` back in; DESIGN.md §12).  Requires the operator path."""
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

    if guard:
        def round_fn(carry, idx):             # idx: (b,)
            alpha, f = carry                  # f = K @ alpha, (m,)
            Gblk = op.cross_block(idx)        # (b, b)
            uTa = f[idx]                      # U^T alpha, free gather
            dalpha = _block_solve(Gblk, uTa, alpha[idx], y[idx], m,
                                  inv_lam)
            df = op.apply_at(idx, dalpha)
            with jax.named_scope(SCATTER):
                return alpha.at[idx].add(dalpha), f + df

        return round_fn

    def round_fn(alpha, idx):                 # idx: (b,)
        if gram_fn is not None:               # materialized m x b slab
            with jax.named_scope(KMV):
                U = gram_fn(A, A[idx], cfg.kernel)
                uTa = U.T @ alpha
            with jax.named_scope(CROSS_BLOCK):
                Gblk = U[idx, :]
        else:                                 # slab-free operator path
            Gblk, uTa = op.round_data(idx, alpha)
        dalpha = _block_solve(Gblk, uTa, alpha[idx], y[idx], m, inv_lam)
        with jax.named_scope(SCATTER):
            return alpha.at[idx].add(dalpha)

    return round_fn


# repro: noqa[CHK-STATIC] gram_fn/op_factory are module-level functions
#   (or None) at every call site; passing a fresh closure retraces by
#   design — it is the documented parity-oracle escape hatch.
@partial(jax.jit, static_argnames=("cfg", "record_every", "gram_fn",
                                   "op_factory"))
def bdcd_krr(A: jnp.ndarray, y: jnp.ndarray, alpha0: jnp.ndarray,
             schedule: jnp.ndarray, cfg: KRRConfig,
             record_every: int = 0,
             gram_fn: Optional[Callable] = None,
             op_factory: Optional[Callable] = None,
             op=None,
             ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Run Algorithm 3 for H = schedule.shape[0] iterations.  ``op``
    injects a prebuilt operator (pytree, crosses jit as data)."""
    round_fn = make_bdcd_round_fn(A, y, cfg, gram_fn=gram_fn,
                                  op_factory=op_factory, op=op)
    res = run_rounds(round_fn, alpha0, schedule,
                     record_state=bool(record_every))
    if record_every:
        return res.state, res.state_hist[record_every - 1::record_every]
    return res.state, None
