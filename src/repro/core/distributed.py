"""Distributed DCD/BDCD solvers under ``shard_map`` — the paper's MPI
implementation (Section 5.2) mapped to JAX mesh collectives.

Layouts
-------
1D (paper):   A is partitioned in 1D-column (feature) layout over the
              ``model`` axis — each device holds ``A[:, n/P]``.  The
              per-iteration kernel-slab reduction ``sum_p A_p B_p^T`` is an
              ``MPI_Allreduce`` in the paper and a ``lax.psum`` here.
              alpha, y and all solver state are replicated, exactly as
              each MPI rank "redundantly stores y and alpha" (Thm 1 proof).

2D (beyond paper): additionally shards samples over the ``data`` axis.
              The model-axis psum then reduces only ``m/P_data x sb``
              words per device, cutting the psum bandwidth term of
              Theorem 2 by P_data at the cost of two extra small
              collectives per round (sampled-row gather + fused
              cross-term gather).  See EXPERIMENTS.md §Perf.

Classical vs s-step: the classical solvers communicate every iteration
(H collectives); the s-step solvers communicate once per outer round
(H/s collectives), which is the paper's entire contribution.

Slab-free (EXPERIMENTS.md §Perf): the solvers consume the kernel slab
through a ``GramOperator``, so these paths keep the psum-before-epilogue
ordering required by nonlinear kernels (Thm 1/2 proofs) but drop the
post-epilogue slab round-trip — the epilogue and the ``U^T alpha``
contraction happen immediately on the psum result, the sampled cross
block is sliced out of the SAME psum (no extra payload), and for the
linear kernel the m x sb reduction disappears entirely (only the
(sb, sb+1) contracted quantities are psummed).

Every collective runs under the ``psum`` named scope, and each
operator's dot blocks and epilogues under ``kmv`` / ``cross_block``
(repro.obs, DESIGN.md §15), as the serial operators name theirs.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.compat import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs.scopes import CROSS_BLOCK, KMV, PSUM, SCATTER

from .bdcd import KRRConfig
from .dcd import SVMConfig
from .kernels import LINEAR, RBF, KernelConfig, apply_epilogue
from .loop import pad_rounds, run_rounds
from .sstep_bdcd import sstep_bdcd_inner, sstep_bdcd_krr
from .sstep_dcd import sstep_dcd_inner, sstep_dcd_ksvm


def _psum(x, axis_name):
    """``lax.psum`` under the ``psum`` named scope."""
    with jax.named_scope(PSUM):
        return jax.lax.psum(x, axis_name)


class AllreduceGramOperator:
    """Slab-free ``GramOperator`` for the paper's 1D-column layout.

    ``round_data`` issues exactly ONE psum per outer round (the paper's
    ideal schedule), after which the slab exists only transiently on-rank:

      linear:    the contraction commutes with the feature reduction, so
                 only ``B (A^T x)`` and ``B B^T`` — (sb, sb+1) words — are
                 psummed; the m x sb slab is NEVER formed, not even
                 pre-epilogue.
      poly/rbf:  the pre-epilogue m x sb dot block must be psummed first
                 (Thm 1/2 ordering); the sampled sb x sb cross-dots are
                 sliced straight out of that psum result (dots[idx] ==
                 the sampled rows' gram, bit-identical), the epilogue
                 runs redundantly on every rank, and ``U^T x`` is
                 contracted immediately — no post-epilogue slab
                 round-trip, no second collective, no extra payload.

    ``row_sqnorms`` (psummed ||a_i||^2, loop-invariant) must be supplied
    for RBF; sampled-column norms are read from it by index instead of a
    separate psum.

    Implements only ``round_data`` — the solvers' entire per-round
    contract; the richer matvec/cross_block/diag surface lives on the
    serial ``GramOperator``.
    """

    def __init__(self, axis_name: str, A_loc, cfg: KernelConfig,
                 row_sqnorms=None):
        if cfg.name == RBF and row_sqnorms is None:
            raise ValueError("RBF AllreduceGramOperator needs the psummed "
                             "row_sqnorms (loop-invariant, compute once)")
        self.axis_name = axis_name
        self.A_loc = A_loc
        self.cfg = cfg
        self.rs = row_sqnorms

    def for_rounds(self):
        """Loop-ready as built: the round reads the rank's rows as they
        are (``GramOperator.for_rounds``)."""
        return self

    def round_data(self, idx, x):
        ax, cfg = self.axis_name, self.cfg
        A_loc = self.A_loc
        B_loc = A_loc[idx]
        r = idx.shape[0]
        if cfg.name == LINEAR:
            with jax.named_scope(CROSS_BLOCK):
                cross_part = B_loc @ B_loc.T              # (r, r) partial
            with jax.named_scope(KMV):
                mv_part = B_loc @ (A_loc.T @ x)           # (r,)  partial
            packed = _psum(
                jnp.concatenate([cross_part, mv_part[:, None]], axis=1), ax)
            return packed[:, :r], packed[:, r]
        with jax.named_scope(KMV):
            dots_part = A_loc @ B_loc.T                   # (m, r) partial
        dots = _psum(dots_part, ax)
        with jax.named_scope(CROSS_BLOCK):
            cross = dots[idx]                             # == psummed B B^T
            cs = self.rs[idx] if cfg.name == RBF else None
            G = apply_epilogue(cross, cfg, cs, cs)
        with jax.named_scope(KMV):
            U = apply_epilogue(dots, cfg, self.rs, cs)    # transient
            return G, U.T @ x


def _psummed_row_sqnorms(A_loc, cfg: KernelConfig, axis_name: str):
    """Loop-invariant psummed ||a_i||^2 (RBF only; None otherwise)."""
    if cfg.name != RBF:
        return None
    return _psum(jnp.sum(A_loc * A_loc, axis=1), axis_name)


# --------------------------------------------------------------------------
# 1D (paper) layout solvers.  The serial solver bodies are reused verbatim:
# only the gram operator changes, which is precisely the paper's claim that
# the s-step schedule is independent of the partitioning.
# --------------------------------------------------------------------------

def dist_sstep_dcd_ksvm(mesh: Mesh, A, y, alpha0, schedule,
                        cfg: SVMConfig, s: int, axis_name: str = "model",
                        op_factory=None):
    """s-step DCD for K-SVM with A in 1D-column layout over ``axis_name``.

    A may be passed as a global array; it is sharded on features by the
    in_spec.  Returns the replicated final alpha.

    ``op_factory(Atil_loc, kernel_cfg)`` injects a custom per-rank
    ``GramOperator`` built from the LOCAL (already diag(y)-scaled) column
    shard — the representation seam of DESIGN.md §9.  For the low-rank
    representation no custom factory is needed: pass ``A = Phi`` with a
    linear kernel config and the default operator reduces only the
    contracted ``(sb, sb+1)``-word round quantities (Phi's l columns are
    what gets sharded, not the raw features).
    """
    spec_A = P(None, axis_name)

    @partial(shard_map, mesh=mesh,
             in_specs=(spec_A, P(), P(), P()), out_specs=P(),
             check_vma=False)
    def run(A_loc, y_r, a0_r, sched_r):
        Atil_loc = y_r[:, None] * A_loc
        rs = _psummed_row_sqnorms(Atil_loc, cfg.kernel, axis_name)

        def default_factory(Atil, kcfg):
            return AllreduceGramOperator(axis_name, Atil, kcfg, rs)

        # pass A_loc (sstep solver re-applies diag(y), idempotent w/ ones)
        out, _ = sstep_dcd_ksvm(A_loc, y_r, a0_r, sched_r, cfg, s,
                                op_factory=op_factory or default_factory)
        return out

    return run(A, y, alpha0, schedule)


def dist_dcd_ksvm(mesh: Mesh, A, y, alpha0, schedule,
                  cfg: SVMConfig, axis_name: str = "model"):
    """Classical DCD baseline (communicates every iteration): implemented
    as s-step with s=1, which degenerates to Algorithm 1's schedule —
    one m-word psum per iteration."""
    return dist_sstep_dcd_ksvm(mesh, A, y, alpha0, schedule, cfg, s=1,
                               axis_name=axis_name)


def dist_sstep_bdcd_krr(mesh: Mesh, A, y, alpha0, schedule,
                        cfg: KRRConfig, s: int, axis_name: str = "model",
                        op_factory=None):
    """s-step BDCD for K-RR, 1D-column layout.  ``op_factory(A_loc,
    kernel_cfg)`` injects a custom per-rank operator (see
    ``dist_sstep_dcd_ksvm``); low-rank runs pass ``A = Phi`` + linear
    config and keep the default."""
    @partial(shard_map, mesh=mesh,
             in_specs=(P(None, axis_name), P(), P(), P()), out_specs=P(),
             check_vma=False)
    def run(A_loc, y_r, a0_r, sched_r):
        rs = _psummed_row_sqnorms(A_loc, cfg.kernel, axis_name)

        def default_factory(A_, kcfg):
            return AllreduceGramOperator(axis_name, A_, kcfg, rs)

        out, _ = sstep_bdcd_krr(A_loc, y_r, a0_r, sched_r, cfg, s,
                                op_factory=op_factory or default_factory)
        return out

    return run(A, y, alpha0, schedule)


def dist_bdcd_krr(mesh: Mesh, A, y, alpha0, schedule,
                  cfg: KRRConfig, axis_name: str = "model"):
    """Classical BDCD baseline — one (m x b)-word psum per iteration."""
    return dist_sstep_bdcd_krr(mesh, A, y, alpha0, schedule, cfg, s=1,
                               axis_name=axis_name)


# --------------------------------------------------------------------------
# 2D (samples x features) s-step solvers — beyond-paper optimization.
# Both drive the shared round protocol (core/loop.py) with a shard_map
# round_fn; the redundant inner phases are the SAME functions the serial
# solvers use (sstep_dcd_inner / sstep_bdcd_inner).
# --------------------------------------------------------------------------

def _gather_rows_onehot(flat, row0, m_loc, dtype):
    """(sb, m_loc) one-hot selector of the globally-indexed sampled rows
    owned by this data-rank; a psum of ``onehot @ X_loc`` IS the gather."""
    return (flat[:, None] == (row0 + jnp.arange(m_loc))[None, :]).astype(
        dtype)


class Sharded2dGramOperator:
    """Per-rank slab-free gram operator for the 2D (samples x features)
    layout — the 2D twin of ``AllreduceGramOperator`` in the operator
    hierarchy (DESIGN.md §9).  Both 2D solver bodies consume ONLY
    ``round_parts``, so a different representation (e.g. a row-sharded
    low-rank factor: pass ``A = Phi`` with a linear kernel config, Phi's
    l columns sharded over ``model``) drops in without touching the
    solver math.

    ``round_parts(flat)`` executes collectives (1)+(2) of the 2D round:
    gather the sampled rows over ``data``, then one ``model`` psum
    reducing the row-local dot block with the sb x sb cross-dots riding
    the same collective.  Returns (onehot, Q_loc, Gblk) — the one-hot
    row selector, the epilogued row-local slab tile, and the replicated
    sampled cross block.
    """

    def __init__(self, A_loc, kernel: KernelConfig, *, data_axis: str,
                 model_axis: str, row0, m_loc: int, row_sqnorms=None):
        self.A_loc = A_loc
        self.kernel = kernel
        self.data_axis = data_axis
        self.model_axis = model_axis
        self.row0 = row0
        self.m_loc = m_loc
        self.rs_loc = row_sqnorms

    def round_parts(self, flat):
        A_loc, kernel, m_loc = self.A_loc, self.kernel, self.m_loc
        with jax.named_scope(KMV):
            onehot = _gather_rows_onehot(flat, self.row0, m_loc,
                                         A_loc.dtype)
            rows_part = onehot @ A_loc
        B_loc = _psum(rows_part, self.data_axis)          # (sb, n_loc)
        sb = flat.shape[0]
        with jax.named_scope(KMV):
            dots_part = A_loc @ B_loc.T                   # (m_loc, sb)
        with jax.named_scope(CROSS_BLOCK):
            cross_part = B_loc @ B_loc.T                  # (sb, sb)
        packed = _psum(jnp.concatenate([dots_part, cross_part], axis=0),
                       self.model_axis)
        dots, cross = packed[:m_loc], packed[m_loc:]
        assert cross.shape[0] == sb
        # RBF: ||b_j||^2 is the cross block's diagonal, free
        cs = jnp.diagonal(cross) if kernel.name == RBF else None
        with jax.named_scope(CROSS_BLOCK):
            Gblk = apply_epilogue(cross, kernel, cs, cs)
        with jax.named_scope(KMV):
            Q_loc = apply_epilogue(dots, kernel, self.rs_loc, cs)
        return onehot, Q_loc, Gblk


def dist_sstep_bdcd_krr_2d(mesh: Mesh, A, y, alpha0, schedule,
                           cfg: KRRConfig, s: int,
                           data_axis: str = "data",
                           model_axis: str = "model",
                           op_factory=None):
    """2D-partitioned s-step BDCD: A[m/Pd, n/Pm] per device, alpha sharded
    over ``data``.  Slab-free: the row-local slab tile is epilogued and
    contracted in one shot; only contracted quantities cross the wires.

    Per outer round the collective schedule is:
      1. psum_data  : gather the s*b sampled rows (s*b x n/Pm words)
      2. psum_model : reduce the row-local dot block PLUS the s*b x s*b
                      cross-dots riding the same collective
                      ((m/Pd + s*b) x s*b words)
      3. psum_data  : fuse {Q^T alpha, alpha at idx, y at idx} into ONE
                      collective (s*b x 3 words — the sb x sb cross block
                      no longer crosses the data axis at all: every rank
                      rebuilds it redundantly from the replicated rows)
    vs. the 1D layout's single psum of (m x s*b).  For m >> s*b*Pd the
    bandwidth term drops by ~Pd while latency grows 3x — a win exactly in
    the paper's bandwidth-bound regime (news20, Fig. 6-7).  RBF row norms
    are loop-invariant and hoisted out of the round loop entirely.

    Ragged H (H % s != 0) runs a masked final short round, exactly as the
    serial solvers do (loop.pad_rounds).  ``op_factory`` overrides the
    per-rank ``Sharded2dGramOperator`` (same constructor signature) —
    the representation seam of DESIGN.md §9.
    """
    m = A.shape[0]
    pd = mesh.shape[data_axis]
    if m % pd != 0:
        raise ValueError(f"m={m} must divide data axis {pd}")
    m_loc = m // pd
    inv_lam = 1.0 / cfg.lam
    b = schedule.shape[1]

    @partial(shard_map, mesh=mesh,
             in_specs=(P(data_axis, model_axis), P(data_axis), P(data_axis),
                       P()),
             out_specs=P(data_axis), check_vma=False)
    def run(A_loc, y_loc, a0_loc, sched):
        my_d = jax.lax.axis_index(data_axis)
        row0 = my_d * m_loc
        # loop-invariant RBF row norms for the locally-owned samples
        rs_loc = _psummed_row_sqnorms(A_loc, cfg.kernel, model_axis)
        op = (op_factory or Sharded2dGramOperator)(
            A_loc, cfg.kernel, data_axis=data_axis, model_axis=model_axis,
            row0=row0, m_loc=m_loc, row_sqnorms=rs_loc)

        def round_fn(alpha_loc, xs):                  # idx: (s, b) global
            idx, valid = xs
            flat = idx.reshape(s * b)
            onehot, Q_loc, Gblk = op.round_parts(flat)
            # (3) contract the slab tile IMMEDIATELY (it never leaves this
            #     scope) and fuse every data-axis cross term into ONE psum.
            with jax.named_scope(KMV):
                QTalpha_loc = Q_loc.T @ alpha_loc      # (sb,)
            packed = jnp.concatenate([
                QTalpha_loc[:, None],                  # (sb, 1)
                (onehot @ alpha_loc)[:, None],         # (sb, 1)
                (onehot @ y_loc)[:, None],             # (sb, 1)
            ], axis=1)
            packed = _psum(packed, data_axis)
            QTalpha = packed[:, 0]
            alpha_at = packed[:, 1].reshape(s, b)
            y_at = packed[:, 2].reshape(s, b)

            # redundant inner loop — shared with the serial solver
            dalpha = sstep_bdcd_inner(Gblk, QTalpha, alpha_at, y_at, flat,
                                      m, inv_lam, s, b, valid)
            # locally-owned scatter-add of the deferred update
            with jax.named_scope(SCATTER):
                return alpha_loc + onehot.T @ dalpha.reshape(s * b)

        xs = pad_rounds(sched, s)
        return run_rounds(round_fn, a0_loc, xs).state

    return run(A, y, alpha0, schedule)


def dist_sstep_dcd_ksvm_2d(mesh: Mesh, A, y, alpha0, schedule,
                           cfg: SVMConfig, s: int,
                           data_axis: str = "data",
                           model_axis: str = "model",
                           op_factory=None):
    """2D-partitioned s-step DCD for K-SVM: Atil[m/Pd, n/Pm] per device,
    alpha and y sharded over ``data``.  Same collective schedule as the
    2D BDCD solver (rows gather -> fused model psum -> fused data psum of
    the contracted round quantities), with the scalar-coordinate inner
    recurrence shared with the serial solver (``sstep_dcd_inner``)."""
    m = A.shape[0]
    pd = mesh.shape[data_axis]
    if m % pd != 0:
        raise ValueError(f"m={m} must divide data axis {pd}")
    m_loc = m // pd
    nu, omega = cfg.nu, cfg.omega

    @partial(shard_map, mesh=mesh,
             in_specs=(P(data_axis, model_axis), P(data_axis), P(data_axis),
                       P()),
             out_specs=P(data_axis), check_vma=False)
    def run(A_loc, y_loc, a0_loc, sched):
        my_d = jax.lax.axis_index(data_axis)
        row0 = my_d * m_loc
        Atil_loc = y_loc[:, None] * A_loc
        rs_loc = _psummed_row_sqnorms(Atil_loc, cfg.kernel, model_axis)
        op = (op_factory or Sharded2dGramOperator)(
            Atil_loc, cfg.kernel, data_axis=data_axis,
            model_axis=model_axis, row0=row0, m_loc=m_loc,
            row_sqnorms=rs_loc)

        def round_fn(alpha_loc, xs):                  # idx: (s,) global
            idx, valid = xs
            onehot, U_loc, G0 = op.round_parts(idx)
            with jax.named_scope(KMV):
                UTalpha_loc = U_loc.T @ alpha_loc      # (s,)
            packed = _psum(jnp.concatenate([
                UTalpha_loc[:, None],                  # (s, 1)
                (onehot @ alpha_loc)[:, None],         # (s, 1)
            ], axis=1), data_axis)
            u_dot_alpha, alpha_at = packed[:, 0], packed[:, 1]

            thetas = sstep_dcd_inner(G0, u_dot_alpha, alpha_at, idx,
                                     nu, omega, s, valid)
            with jax.named_scope(SCATTER):
                return alpha_loc + onehot.T @ thetas

        xs = pad_rounds(sched, s)
        return run_rounds(round_fn, a0_loc, xs).state

    return run(A, y, alpha0, schedule)


def shard_dataset_1d(mesh: Mesh, A, axis_name: str = "model"):
    """Place a host array in the paper's 1D-column layout on the mesh."""
    return jax.device_put(A, NamedSharding(mesh, P(None, axis_name)))
