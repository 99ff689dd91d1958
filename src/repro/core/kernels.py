"""Kernel functions (paper Table 1), gram-slab computation, and the
``GramOperator`` representation hierarchy (DESIGN.md §2/§9).

The paper's hot spot is ``K(A, Omega_k^T A)`` — an ``m x (s*b)`` slab of the
full ``m x m`` kernel matrix.  On TPU this is a GEMM (MXU) followed by a
pointwise epilogue (VPU).  ``gram_slab`` below is the pure-jnp reference
path; the Pallas fused kernel lives in ``repro.kernels.gram`` and is
numerically validated against this implementation.

Solvers and the predict subsystem never consume slabs directly: they go
through a ``GramOperator`` — ``ExactGramOperator`` (raw features +
kernel config, KMV-streamed) or ``LowRankGramOperator`` (Nystrom/feature
factor ``Phi``, every reduction O(l)-wide) — so the kernel
*representation* swaps without touching solver or serving math.

Operators are registered pytrees, which is also what makes solver
FLEETS cheap (repro.tune, DESIGN.md §10): under ``jax.vmap`` an
operator closed over (or passed) unbatched stays unbatched, so the slab
GEMM and epilogue of ``matvec``/``round_data`` are computed once per
round for all F vmapped members — only the contraction against the
batched right-hand side replicates.

Every concrete operator runs ``matvec`` under the ``kmv`` named scope,
``cross_block`` under ``cross_block`` and ``apply_at`` under
``kmv_apply`` (repro.obs, DESIGN.md §15), so each solver's round shows
its phases in a device trace whichever driver runs it.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.obs.scopes import CROSS_BLOCK, KMV, KMV_APPLY, scoped

LINEAR = "linear"
POLYNOMIAL = "polynomial"
RBF = "rbf"

_VALID = (LINEAR, POLYNOMIAL, RBF)


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Configuration of the kernel function K (paper Table 1).

    linear:      K(x, z) = x.z
    polynomial:  K(x, z) = (c + x.z)^d          (c >= 0, d >= 2)
    rbf:         K(x, z) = exp(-sigma ||x-z||^2) (sigma > 0)
    """

    name: str = RBF
    degree: int = 3
    coef0: float = 0.0
    sigma: float = 1.0

    def __post_init__(self):
        if self.name not in _VALID:
            raise ValueError(f"unknown kernel {self.name!r}; expected one of {_VALID}")


def apply_epilogue(dots: jnp.ndarray, cfg: KernelConfig,
                   row_sqnorms: Optional[jnp.ndarray] = None,
                   col_sqnorms: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Pointwise kernel epilogue applied to a block of dot products.

    ``dots[i, j] = a_i . b_j``.  For RBF the squared norms of the rows of A
    (``row_sqnorms``) and of B (``col_sqnorms``) must be supplied so that
    ``||a_i - b_j||^2 = ||a_i||^2 + ||b_j||^2 - 2 a_i.b_j``.
    """
    if cfg.name == LINEAR:
        return dots
    if cfg.name == POLYNOMIAL:
        return (cfg.coef0 + dots) ** cfg.degree
    # RBF
    assert row_sqnorms is not None and col_sqnorms is not None
    sq = row_sqnorms[:, None] + col_sqnorms[None, :] - 2.0 * dots
    # Clamp tiny negative values produced by cancellation so exp stays <= 1
    sq = jnp.maximum(sq, 0.0)
    return jnp.exp(-cfg.sigma * sq)


@partial(jax.jit, static_argnames=("cfg",))
def gram_slab(A: jnp.ndarray, B: jnp.ndarray, cfg: KernelConfig) -> jnp.ndarray:
    """Compute the kernel slab ``K(A, B) in R^{m x r}``.

    A: (m, n) full (or feature-sharded) data matrix.
    B: (r, n) the sampled rows ``Omega_k^T A`` (same feature layout as A).
    """
    dots = A @ B.T
    if cfg.name == RBF:
        rs = jnp.sum(A * A, axis=1)
        cs = jnp.sum(B * B, axis=1)
        return apply_epilogue(dots, cfg, rs, cs)
    return apply_epilogue(dots, cfg)


def gram_full(A: jnp.ndarray, cfg: KernelConfig) -> jnp.ndarray:
    """Full m x m kernel matrix (only for oracles / closed-form solves)."""
    return gram_slab(A, A, cfg)


def kernel_diag(B: jnp.ndarray, cfg: KernelConfig) -> jnp.ndarray:
    """``diag K(B, B)`` without forming the block: (r,) for B: (r, n)."""
    sq = jnp.sum(B * B, axis=1)
    if cfg.name == LINEAR:
        return sq
    if cfg.name == POLYNOMIAL:
        return (cfg.coef0 + sq) ** cfg.degree
    return jnp.ones_like(sq)                     # RBF: K(x, x) = 1


def _pad_rows(X: jnp.ndarray, rows: int) -> jnp.ndarray:
    """``X`` with zero rows appended up to ``rows`` (itself if it has
    them already)."""
    pad = rows - X.shape[0]
    if not pad:
        return X
    return jnp.pad(X, ((0, pad),) + ((0, 0),) * (X.ndim - 1))


def kmv_pad_rows(m: int, cfg: KernelConfig, block: int = 2048) -> int:
    """Zero rows the blocked KMV appends to an m-row A: up to a whole
    number of ``min(block, m)``-row blocks; none for the linear kernel,
    which contracts without blocks."""
    if cfg.name == LINEAR:
        return 0
    return (-m) % min(block, m)


def kmv_slab_free(A: jnp.ndarray, B: jnp.ndarray, X: jnp.ndarray,
                  cfg: KernelConfig, block: int = 2048) -> jnp.ndarray:
    """``U^T X`` with ``U = K(A, B)`` — without an ``m x r`` slab (DESIGN.md
    §2).

    linear:    U^T X = B (A^T X) — pure algebra, the slab never exists.
    poly/rbf:  blocked scan over m; each (block x r) kernel tile is built,
               contracted against its X chunk, and discarded, so peak extra
               memory is O(block * r) instead of O(m * r).  The Pallas KMV
               kernel (``repro.kernels.kmv``) is the fused on-chip version
               of exactly this loop.

    X: (m,) or (m, c) right-hand vectors; returns (r,) / (r, c).  A may
    carry zero rows past X's m (``ExactGramOperator.for_rounds``): X is
    padded to A's rows, and A is padded only when its rows are not a
    whole number of blocks.
    """
    vec = X.ndim == 1
    Xc = _pad_rows(X[:, None] if vec else X, A.shape[0])  # zero rows: no-op
    if cfg.name == LINEAR:
        out = B @ (A.T @ Xc)                            # (r, c)
    else:
        m, n = A.shape
        r = B.shape[0]
        c = Xc.shape[1]
        blk = min(block, m)
        mp = m + kmv_pad_rows(m, cfg, block)
        Ap = _pad_rows(A, mp)
        Xp = _pad_rows(Xc, mp)
        cs = jnp.sum(B * B, axis=1) if cfg.name == RBF else None

        def body(acc, chunk):
            a_blk, x_blk = chunk
            dots = a_blk @ B.T                          # (blk, r)
            if cfg.name == RBF:
                Kb = apply_epilogue(dots, cfg,
                                    jnp.sum(a_blk * a_blk, axis=1), cs)
            else:
                Kb = apply_epilogue(dots, cfg)
            return acc + Kb.T @ x_blk, None

        out, _ = jax.lax.scan(
            body, jnp.zeros((r, c), Xc.dtype),
            (Ap.reshape(-1, blk, n), Xp.reshape(-1, blk, c)))
    return out[:, 0] if vec else out


def kmv_apply(A: jnp.ndarray, B: jnp.ndarray, w: jnp.ndarray,
              cfg: KernelConfig, block: int = 2048) -> jnp.ndarray:
    """``K(A, B) @ w`` — the adjoint of ``kmv_slab_free``'s reduction,
    without an ``m x r`` slab (DESIGN.md §12).

    This is the residual-recurrence update of the guarded solvers:
    after a round changes ``alpha`` by ``w`` on the sampled coordinates,
    ``f = K alpha`` advances by ``K[:, idx] @ w = K(A, A[idx]) @ w``.
    Kernel-evaluation count is identical to the ``U^T alpha`` matvec the
    recurrence replaces (m x r either way), so guarded rounds stay
    cost-neutral between drift corrections.

    linear:    K(A, B) w = A (B^T w) — pure algebra, slab-free.
    poly/rbf:  blocked scan over m; each (block x r) kernel tile is
               built, applied to w, and discarded.

    w: (r,) or (r, c); returns (m,) / (m, c), one row per row of A.
    """
    vec = w.ndim == 1
    Wc = w[:, None] if vec else w
    if cfg.name == LINEAR:
        out = A @ (B.T @ Wc)                            # (m, c)
    else:
        m, n = A.shape
        blk = min(block, m)
        Ap = _pad_rows(A, m + kmv_pad_rows(m, cfg, block))
        cs = jnp.sum(B * B, axis=1) if cfg.name == RBF else None

        def body(carry, a_blk):
            dots = a_blk @ B.T                          # (blk, r)
            if cfg.name == RBF:
                Kb = apply_epilogue(dots, cfg,
                                    jnp.sum(a_blk * a_blk, axis=1), cs)
            else:
                Kb = apply_epilogue(dots, cfg)
            return carry, Kb @ Wc                       # (blk, c)

        _, tiles = jax.lax.scan(body, 0.0, Ap.reshape(-1, blk, n))
        out = tiles.reshape(-1, Wc.shape[1])[:m]
    return out[:, 0] if vec else out


class GramOperator:
    """Abstract kernel *representation*: slab-free access to the gram
    matrix ``K`` of a fixed training set (DESIGN.md §9).

    Every solver in ``repro.core`` consumes the ``m x (s*b)`` slab
    ``U = K(A, A[idx])`` through exactly three reductions, so exposing only
    those lets backends (fused Pallas KMV, shard_map all-reduce, low-rank
    feature maps) never materialize ``U`` in HBM:

      ``matvec(idx, X)``    -> ``U^T X``            (s*b,) or (s*b, c)
      ``cross_block(idx)``  -> ``U[idx, :]``        (s*b, s*b) sampled gram
      ``diag(idx)``         -> ``diag K`` at idx    (s*b,)

    ``round_data(idx, X)`` bundles (cross_block, matvec) — the per-round
    needs of the s-step solvers — so distributed implementations can fuse
    both into one collective (see ``core.distributed``).

    The serving surface (``core/predict.py``) adds two more reductions:

      ``serve_weights(w)``     -> representation-side precompute of the
                                  model weights (identity for exact,
                                  ``Phi^T w`` — (l,) words — for low-rank)
      ``serve_block(Xq, sw)``  -> ``K(Xq, train) @ w`` for one query block

    plus ``scale_rows(y)`` (the solvers' ``diag(y)`` data scaling) and
    ``take(idx)`` (support-vector compaction), both returning a NEW
    operator over the transformed representation.

    Concrete backends: ``ExactGramOperator`` (raw features + kernel
    config), ``LowRankGramOperator`` (Nystrom/feature-map factor ``Phi``),
    and ``core.distributed.AllreduceGramOperator`` (1D shard_map psum
    fusion, round_data only).  All are registered jax pytrees, so a
    prebuilt operator can cross ``jit`` boundaries as a plain argument.
    """

    def __init__(self, *args, **kwargs):
        raise TypeError(
            "GramOperator is the abstract representation interface "
            "(DESIGN.md §9); construct a concrete backend instead — "
            "ExactGramOperator(A, cfg, ...) is the former concrete "
            "GramOperator")

    def rows(self, idx: jnp.ndarray) -> jnp.ndarray:
        raise NotImplementedError

    def matvec(self, idx: jnp.ndarray, X: jnp.ndarray) -> jnp.ndarray:
        raise NotImplementedError

    def cross_block(self, idx: jnp.ndarray) -> jnp.ndarray:
        raise NotImplementedError

    def diag(self, idx: jnp.ndarray) -> jnp.ndarray:
        raise NotImplementedError

    @property
    def n_samples(self) -> int:
        raise NotImplementedError

    @property
    def feature_dim(self) -> Optional[int]:
        """Width of the RAW query rows ``serve_block`` accepts, or None
        when the representation cannot serve new points (a low-rank
        factor without its feature map).  The serve-side eager
        validators (``core.predict.validate_queries``,
        ``serve.engine.ServingEngine.submit``) check incoming queries
        against this instead of letting a shape mismatch explode inside
        jit with an unattributable error."""
        raise NotImplementedError

    @property
    def dtype(self):
        """Dtype of the representation's data leaves — what query blocks
        must arrive as (serving never silently up/down-casts)."""
        raise NotImplementedError

    def scale_rows(self, y: jnp.ndarray) -> "GramOperator":
        raise NotImplementedError

    def take(self, idx) -> "GramOperator":
        raise NotImplementedError

    def serve_weights(self, w: jnp.ndarray) -> jnp.ndarray:
        """Representation-side precompute for serving (default: identity).

        ``w`` may be one model (m,) or F stacked models (m, F) — e.g. a
        solver fleet's solutions (repro.tune): the precompute and every
        ``serve_block`` call then serve ALL F models in one sweep (the
        cross-validation scorer grades a whole regularization grid with
        a single KMV per validation fold)."""
        return w

    def serve_block(self, Xq: jnp.ndarray, sw: jnp.ndarray) -> jnp.ndarray:
        """``K(Xq, train) @ w`` for one (q, n) query block, slab-free;
        (q,) for one model, (q, F) for stacked fleet weights."""
        raise NotImplementedError

    def round_data(self, idx: jnp.ndarray, X: jnp.ndarray):
        """(cross_block, matvec) for one s-step round."""
        return self.cross_block(idx), self.matvec(idx, X)

    @property
    def round_pad_rows(self) -> int:
        """Zero rows the loop-ready form (``for_rounds``) carries past
        the true ``n_samples``."""
        return 0

    def for_rounds(self) -> "GramOperator":
        """The operator in the form a round loop reads: built once per
        solve, before the loop, so that no round repeats work that only
        depends on the training data.  The round-function factories call
        it; it is the operator itself unless a backend says otherwise."""
        return self

    # -- guarded-solve surface (repro.resilience, DESIGN.md §12) --------

    def apply_at(self, idx: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
        """``K[:, idx] @ w`` — the residual-recurrence update: after a
        round adds ``w`` to ``alpha[idx]``, ``f = K alpha`` advances by
        exactly this column combination.  (m,) for w: (s*b,)."""
        raise NotImplementedError

    def full_matvec(self, X: jnp.ndarray) -> jnp.ndarray:
        """``K @ X`` computed EXACTLY (one full kernel matvec) — the
        drift-correction / residual-replacement primitive and the
        residual initializer for warm starts.  (m,) for X: (m,)."""
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class ExactGramOperator(GramOperator):
    """Exact-kernel representation: raw features + kernel config; the
    reductions stream the slab through ``kmv_slab_free`` (or a Pallas KMV
    backend via ``matvec_impl(A, B, X, cfg)``, see ``kernels.ops``).

    ``for_rounds()`` gives the loop-ready form: A zero-padded to a whole
    number of KMV blocks once per solve, so that the blocked KMV pads
    nothing per round.  ``m`` (static) keeps its true row count; every
    reduction takes and gives per-row arrays at ``m`` rows, so the
    padding shows nowhere outside.  ``m`` is None for an unpadded A."""

    A: jnp.ndarray
    cfg: KernelConfig
    matvec_impl: Optional[callable] = None
    block: int = 2048
    m: Optional[int] = None

    def rows(self, idx: jnp.ndarray) -> jnp.ndarray:
        return self.A[idx]

    def _kmv(self, B: jnp.ndarray, X: jnp.ndarray) -> jnp.ndarray:
        """``K(A, B)^T X`` through the backend; X has the true m rows."""
        if self.matvec_impl is not None:
            return self.matvec_impl(self.A, B, X, self.cfg)
        return kmv_slab_free(self.A, B, X, self.cfg, block=self.block)

    @scoped(KMV)
    def matvec(self, idx: jnp.ndarray, X: jnp.ndarray) -> jnp.ndarray:
        return self._kmv(self.A[idx], X)

    @scoped(CROSS_BLOCK)
    def cross_block(self, idx: jnp.ndarray) -> jnp.ndarray:
        B = self.A[idx]
        return gram_slab(B, B, self.cfg)

    def diag(self, idx: jnp.ndarray) -> jnp.ndarray:
        return kernel_diag(self.A[idx], self.cfg)

    @property
    def n_samples(self) -> int:
        return self.A.shape[0] if self.m is None else self.m

    @property
    def round_pad_rows(self) -> int:
        # a backend (matvec_impl) tiles A itself: only the blocked jnp
        # KMV pads it
        if self.matvec_impl is not None:
            return 0
        return kmv_pad_rows(self.n_samples, self.cfg, self.block)

    def for_rounds(self) -> "ExactGramOperator":
        rows = self.n_samples + self.round_pad_rows
        if self.A.shape[0] == rows:
            return self
        return dataclasses.replace(self, A=_pad_rows(self.A, rows),
                                   m=self.n_samples)

    @property
    def feature_dim(self) -> int:
        return self.A.shape[1]

    @property
    def dtype(self):
        return self.A.dtype

    def scale_rows(self, y: jnp.ndarray) -> "ExactGramOperator":
        """Operator over ``diag(y) A`` — the solvers' K-SVM data scaling
        (the paper implementation's convention, preserved verbatim).
        NOTE: for nonlinear kernels ``K(diag(y) A)`` is NOT
        ``diag(y) K diag(y)`` — see ``LowRankGramOperator.scale_rows``
        for the semantic consequence."""
        return dataclasses.replace(self, A=y[:, None] * self.A)

    def take(self, idx) -> "ExactGramOperator":
        return dataclasses.replace(self, A=self.A[idx], m=None)

    def serve_block(self, Xq: jnp.ndarray, sw: jnp.ndarray) -> jnp.ndarray:
        # K(A, Xq)^T sw == K(Xq, A) @ sw: one KMV with the queries as the
        # sampled rows — slab-free over the (large) training dimension.
        return self._kmv(Xq, sw)

    @scoped(KMV_APPLY)
    def apply_at(self, idx: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
        # always streams through the jnp KMV adjoint: the Pallas
        # matvec_impl accelerates the U^T X reduction only — apply_at's
        # tile loop runs over the m axis instead and its tiles are the
        # same size, so there is nothing kernel-shaped to gain here
        out = kmv_apply(self.A, self.A[idx], w, self.cfg, block=self.block)
        return out[:self.n_samples]

    def full_matvec(self, X: jnp.ndarray) -> jnp.ndarray:
        # K symmetric: K @ X == K(A, A)^T X — one full-width KMV
        return self._kmv(self.A[:self.n_samples], X)


@dataclasses.dataclass(frozen=True)
class LowRankGramOperator(GramOperator):
    """Low-rank representation ``K ~= Phi Phi^T`` (Nystrom, random
    features, ...): every reduction is an O(l)-width *linear*-kernel
    contraction over the factor ``Phi in R^{m x l}`` — the slab, the
    cross block, and the diagonal never touch the raw features or the
    nonlinear epilogue again.

    ``fmap`` (optional, e.g. ``nystrom.NystromMap``) maps NEW points into
    the same feature space; it is required only by the serving surface
    (``serve_block``), not by training.
    """

    Phi: jnp.ndarray
    fmap: Optional[object] = None

    def rows(self, idx: jnp.ndarray) -> jnp.ndarray:
        return self.Phi[idx]

    @scoped(KMV)
    def matvec(self, idx: jnp.ndarray, X: jnp.ndarray) -> jnp.ndarray:
        return self.Phi[idx] @ (self.Phi.T @ X)

    @scoped(CROSS_BLOCK)
    def cross_block(self, idx: jnp.ndarray) -> jnp.ndarray:
        R = self.Phi[idx]
        return R @ R.T

    def diag(self, idx: jnp.ndarray) -> jnp.ndarray:
        R = self.Phi[idx]
        return jnp.sum(R * R, axis=1)

    @property
    def n_samples(self) -> int:
        return self.Phi.shape[0]

    @property
    def rank(self) -> int:
        return self.Phi.shape[1]

    @property
    def feature_dim(self) -> Optional[int]:
        # queries arrive in RAW feature space and go through the map;
        # without a map the operator cannot serve new points at all
        if self.fmap is None:
            return None
        return self.fmap.landmarks.shape[1]

    @property
    def dtype(self):
        return self.Phi.dtype

    def scale_rows(self, y: jnp.ndarray) -> "LowRankGramOperator":
        """``diag(y) K~ diag(y) == (diag(y) Phi)(diag(y) Phi)^T``
        exactly — the textbook K-SVM dual scaling, consistent with
        ``objectives._Qbar`` and the serving expansion.  This differs
        from the exact path's ``K(diag(y) A)`` convention for NONLINEAR
        kernels (where feature scaling does not commute with the
        epilogue), so exact vs low-rank K-SVM solutions are directly
        comparable only for the linear kernel; each path is internally
        consistent (training dual == stopping metric == serving)."""
        return dataclasses.replace(self, Phi=y[:, None] * self.Phi)

    def take(self, idx) -> "LowRankGramOperator":
        return dataclasses.replace(self, Phi=self.Phi[idx])

    def serve_weights(self, w: jnp.ndarray) -> jnp.ndarray:
        return self.Phi.T @ w                     # (l,) — the whole model

    def serve_block(self, Xq: jnp.ndarray, sw: jnp.ndarray) -> jnp.ndarray:
        if self.fmap is None:
            raise ValueError(
                "LowRankGramOperator has no feature map (fmap=None): "
                "serving new points needs one — build the operator via "
                "repro.core.nystrom.fit_nystrom / the repro.api facade "
                "(SolverOptions(approx='nystrom'))")
        return self.fmap(Xq) @ sw                 # O(l) per query

    @scoped(KMV_APPLY)
    def apply_at(self, idx: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
        return self.Phi @ (self.Phi[idx].T @ w)   # O(m l), no slab

    def full_matvec(self, X: jnp.ndarray) -> jnp.ndarray:
        return self.Phi @ (self.Phi.T @ X)        # O(m l) exact in K~


def _chunk(X, chunk_rows: int):
    """(m, ...) -> (nc, chunk_rows, ...) with a zero-padded tail chunk."""
    nc = -(-X.shape[0] // chunk_rows)
    X = _pad_rows(X, nc * chunk_rows)
    return X.reshape((nc, chunk_rows) + X.shape[1:])


@dataclasses.dataclass(frozen=True)
class StreamingGramOperator(GramOperator):
    """Out-of-core exact-kernel representation (DESIGN.md §14): the data
    lives CHUNKED as ``Xc: (n_chunks, chunk_rows, n)`` row blocks — on
    device this is the layout the double-buffered streaming KMV kernel
    (``kernels/kmv_stream.py``) DMAs from ANY/HBM memory two slots at a
    time, so no reduction ever holds the full X (or any m-tall slab) in
    its working set.  Every ``GramOperator`` reduction is a scan over
    the chunk axis:

      ``matvec``/``serve_block``/``full_matvec``  accumulate
          ``K(chunk_i, B)^T x_i`` chunk by chunk (the streamed KMV —
          fused in the Pallas kernel when ``matvec_impl`` is set);
      ``apply_at``  emits ``K(chunk_i, B) @ w`` piece by piece (the
          guard path's residual recurrence);
      ``cross_block``/``diag``/``rows``  gather only the sampled
          ``sb`` rows (two tiny index ops per chunk-crossing gather).

    The tail chunk is zero-padded; padded rows are contraction-safe
    (their right-hand-side rows are zero) and sliced off wherever rows
    are EMITTED.  ``m`` is the true row count.  A registered pytree like
    the resident operators, so it crosses jit boundaries, vmaps
    unbatched under solver fleets, and drops into all four round-fn
    factories, the guard, and the batched predictor unchanged.
    """

    Xc: jnp.ndarray                        # (nc, chunk_rows, n)
    cfg: KernelConfig
    m: int                                 # true rows (static)
    matvec_impl: Optional[callable] = None  # (Xc, B, Xvc, cfg) -> (r, c)

    @classmethod
    def from_dense(cls, A: jnp.ndarray, cfg: KernelConfig,
                   chunk_rows: int, matvec_impl=None
                   ) -> "StreamingGramOperator":
        if not isinstance(chunk_rows, int) or chunk_rows < 1:
            raise ValueError(f"chunk_rows must be a positive int, got "
                             f"{chunk_rows!r}")
        chunk_rows = min(chunk_rows, A.shape[0])
        return cls(_chunk(A, chunk_rows), cfg, A.shape[0],
                   matvec_impl=matvec_impl)

    @property
    def chunk_rows(self) -> int:
        return self.Xc.shape[1]

    @property
    def n_chunks(self) -> int:
        return self.Xc.shape[0]

    def rows(self, idx: jnp.ndarray) -> jnp.ndarray:
        cr = self.chunk_rows
        return self.Xc[idx // cr, idx % cr]

    def _chunk_rhs(self, X):
        """Chunk an (m, c) right-hand side to the Xc layout."""
        return _chunk(X, self.chunk_rows)

    def _stream_kmv(self, B: jnp.ndarray, X: jnp.ndarray) -> jnp.ndarray:
        """``K(A, B)^T X`` streamed over the chunk axis: the core
        contraction behind matvec / serve_block / full_matvec."""
        vec = X.ndim == 1
        Xvc = self._chunk_rhs(X[:, None] if vec else X)  # (nc, cr, c)
        if self.matvec_impl is not None:
            out = self.matvec_impl(self.Xc, B, Xvc, self.cfg)
        else:
            cfg = self.cfg
            cs = jnp.sum(B * B, axis=1) if cfg.name == RBF else None

            def body(acc, chunk):
                a_blk, x_blk = chunk
                dots = a_blk @ B.T                       # (cr, r)
                if cfg.name == RBF:
                    Kb = apply_epilogue(dots, cfg,
                                        jnp.sum(a_blk * a_blk, axis=1),
                                        cs)
                else:
                    Kb = apply_epilogue(dots, cfg)
                return acc + Kb.T @ x_blk, None

            out, _ = jax.lax.scan(
                body, jnp.zeros((B.shape[0], Xvc.shape[2]), X.dtype),
                (self.Xc, Xvc))
        out = out.astype(X.dtype)
        return out[:, 0] if vec else out

    @scoped(KMV)
    def matvec(self, idx: jnp.ndarray, X: jnp.ndarray) -> jnp.ndarray:
        return self._stream_kmv(self.rows(idx), X)

    @scoped(CROSS_BLOCK)
    def cross_block(self, idx: jnp.ndarray) -> jnp.ndarray:
        B = self.rows(idx)
        return gram_slab(B, B, self.cfg)

    def diag(self, idx: jnp.ndarray) -> jnp.ndarray:
        return kernel_diag(self.rows(idx), self.cfg)

    @property
    def n_samples(self) -> int:
        return self.m

    @property
    def feature_dim(self) -> int:
        return self.Xc.shape[2]

    @property
    def dtype(self):
        return self.Xc.dtype

    def scale_rows(self, y: jnp.ndarray) -> "StreamingGramOperator":
        """Operator over ``diag(y) A`` (K-SVM scaling, same convention
        as ``ExactGramOperator.scale_rows``), chunked in place — the
        padded tail rows of y are zero, so padded data rows stay zero."""
        yc = self._chunk_rhs(y[:, None])                 # (nc, cr, 1)
        return dataclasses.replace(self, Xc=yc * self.Xc)

    def take(self, idx) -> "StreamingGramOperator":
        """Support-vector compaction (host-side, concrete idx): gather
        the kept rows and re-chunk."""
        kept = self.rows(jnp.asarray(idx))
        cr = min(self.chunk_rows, kept.shape[0])
        return dataclasses.replace(self, Xc=_chunk(kept, cr),
                                   m=kept.shape[0])

    def serve_block(self, Xq: jnp.ndarray, sw: jnp.ndarray) -> jnp.ndarray:
        # K(Xq, A) @ sw == K(A, Xq)^T sw: the queries ARE the sampled
        # rows — one streamed KMV, same pipe as training
        return self._stream_kmv(Xq, sw)

    @scoped(KMV_APPLY)
    def apply_at(self, idx: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
        """``K[:, idx] @ w`` emitted chunk by chunk (the guard path's
        residual recurrence): each chunk builds its (cr, sb) kernel tile
        against the sampled rows, applies w, and is discarded."""
        B = self.rows(idx)
        cfg = self.cfg
        vec = w.ndim == 1
        Wc = w[:, None] if vec else w
        cs = jnp.sum(B * B, axis=1) if cfg.name == RBF else None

        def body(carry, a_blk):
            dots = a_blk @ B.T                           # (cr, sb)
            if cfg.name == RBF:
                Kb = apply_epilogue(dots, cfg,
                                    jnp.sum(a_blk * a_blk, axis=1), cs)
            else:
                Kb = apply_epilogue(dots, cfg)
            return carry, Kb @ Wc                        # (cr, c)

        _, tiles = jax.lax.scan(body, 0.0, self.Xc)
        out = tiles.reshape(-1, Wc.shape[1])[:self.m].astype(Wc.dtype)
        return out[:, 0] if vec else out

    def full_matvec(self, X: jnp.ndarray) -> jnp.ndarray:
        """``K @ X`` exactly, chunk x chunk: the j-th output piece is
        ``K(chunk_j, A) @ X = K(A, chunk_j)^T X`` — one streamed KMV per
        chunk (nc^2 tiles total, never more than one in flight)."""
        vec = X.ndim == 1

        def piece(_, b_blk):
            return _, self._stream_kmv(b_blk, X)         # (cr,) / (cr, c)

        _, tiles = jax.lax.scan(piece, 0.0, self.Xc)
        out = (tiles.reshape(-1) if vec
               else tiles.reshape(-1, X.shape[1]))[:self.m]
        return out.astype(X.dtype)


jax.tree_util.register_dataclass(
    ExactGramOperator, data_fields=("A",),
    meta_fields=("cfg", "matvec_impl", "block", "m"))
jax.tree_util.register_dataclass(
    LowRankGramOperator, data_fields=("Phi", "fmap"), meta_fields=())
jax.tree_util.register_dataclass(
    StreamingGramOperator, data_fields=("Xc",),
    meta_fields=("cfg", "m", "matvec_impl"))
