"""Fused kernel-matrix·vector (KMV) Pallas TPU kernel.

Computes ``U^T X`` with ``U = K(A, B)`` — the slab-free contraction behind
``core.kernels.GramOperator.matvec`` — WITHOUT ever materializing the
``m x r`` kernel slab in HBM (DESIGN.md §2, EXPERIMENTS.md §Perf).

The s-step solvers only ever consume the slab through ``U^T alpha`` (plus
the tiny ``(sb x sb)`` cross block computed separately), yet the
materialized path writes and re-reads all ``m * s*b`` words every round.
This kernel streams ``(bm x bk)`` tiles of A, runs the GEMM on the MXU,
applies the Table-1 epilogue (linear/poly/RBF with folded row/col squared
norms) on the VPU while the f32 accumulator tile is VMEM-resident, then
immediately contracts the finished ``(bm x br)`` kernel tile against the
matching ``(c x bm)`` X^T tile (second MXU op) into a ``(c x br)`` VMEM
accumulator.  HBM traffic per round: read A once, read B, read X — zero
slab bytes (the ``2 * m * s*b`` word round-trip of a materialized slab
disappears).

Grid: (r/br, m/bm, n/bk) = (j, i, k); j parallel, i and k arbitrary so the
(c x br) output block stays resident across the whole (i, k) sweep.

The same contraction serves PREDICTION (DESIGN.md §9): with B = a query
block and X = the model weights, ``U^T X = K(Xq, A_train) @ w`` — the
batched predict subsystem (``core/predict.py``) tiles queries through
this kernel via ``ExactGramOperator.serve_block``, so the ``q x m``
test-kernel slab never exists either; the j-parallel grid axis then
ranges over queries, which is embarrassingly parallel across serving
batches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.kernels import LINEAR, POLYNOMIAL, RBF, KernelConfig
from .gram import _CompilerParams, _pad_to, _round_up, _sublane


def _kmv_kernel(a_ref, b_ref, xt_ref, o_ref, acc_ref, oacc_ref, rs_ref,
                cs_ref, *, kernel_name: str, degree: int, coef0: float,
                sigma: float, m_steps: int, k_steps: int):
    i = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(jnp.logical_and(i == 0, k == 0))
    def _init_out():
        oacc_ref[...] = jnp.zeros_like(oacc_ref)

    @pl.when(k == 0)
    def _init_tile():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if kernel_name == RBF:
            rs_ref[...] = jnp.zeros_like(rs_ref)
            cs_ref[...] = jnp.zeros_like(cs_ref)

    a = a_ref[...]                                   # (bm, bk)
    b = b_ref[...]                                   # (br, bk)
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # MXU
    if kernel_name == RBF:
        af = a.astype(jnp.float32)
        bf = b.astype(jnp.float32)
        rs_ref[...] += jnp.sum(af * af, axis=1, keepdims=True)
        cs_ref[...] += jnp.sum(bf * bf, axis=1, keepdims=True)

    @pl.when(k == k_steps - 1)
    def _epilogue_and_contract():                    # VPU then MXU, in VMEM
        dots = acc_ref[...]
        if kernel_name == LINEAR:
            ktile = dots
        elif kernel_name == POLYNOMIAL:
            ktile = (coef0 + dots) ** degree
        else:                                        # RBF
            sq = rs_ref[...] + cs_ref[...].T - 2.0 * dots
            ktile = jnp.exp(-sigma * jnp.maximum(sq, 0.0))
        xt = xt_ref[...].astype(jnp.float32)         # (c, bm)
        oacc_ref[...] += jax.lax.dot_general(
            xt, ktile, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # (c, br)

    @pl.when(jnp.logical_and(i == m_steps - 1, k == k_steps - 1))
    def _emit():
        o_ref[...] = oacc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "cfg", "bm", "br", "bk", "interpret", "out_dtype"))
def kmv_pallas(A: jnp.ndarray, B: jnp.ndarray, X: jnp.ndarray,
               cfg: KernelConfig, *, bm: int = 128, br: int = 128,
               bk: int = 512, interpret: bool = False,
               out_dtype=jnp.float32):
    """``U^T X`` for ``U = K(A, B)``; A: (m, n), B: (r, n), X: (m,)|(m, c).

    Returns (r,) / (r, c) in ``out_dtype``.  Shapes need not be
    block-aligned — inputs are zero-padded and the output sliced back.
    Padding is contraction-safe: padded X rows are zero, so the (nonzero
    for RBF/poly) kernel values of padded A rows contribute nothing, and
    padded B columns are sliced off before any consumer sees them.
    """
    vec = X.ndim == 1
    Xt = (X[None, :] if vec else X.T)                # (c, m)
    m, n = A.shape
    r, n2 = B.shape
    assert n == n2 and Xt.shape[1] == m, (A.shape, B.shape, X.shape)
    c = Xt.shape[0]

    sub = max(_sublane(A.dtype), _sublane(Xt.dtype))
    bm_ = _round_up(min(bm, _round_up(m, sub)), sub)
    br_ = _round_up(min(br, _round_up(r, sub)), sub)
    bk_ = min(bk, _round_up(n, 128))
    c_ = _round_up(c, sub)

    Ap = _pad_to(_pad_to(A, bm_, 0), bk_, 1)
    Bp = _pad_to(_pad_to(B, br_, 0), bk_, 1)
    Xp = _pad_to(_pad_to(Xt, c_, 0), bm_, 1)
    M, N = Ap.shape
    R = Bp.shape[0]
    m_steps, k_steps = M // bm_, N // bk_
    grid = (R // br_, m_steps, k_steps)

    kern = functools.partial(
        _kmv_kernel, kernel_name=cfg.name, degree=cfg.degree,
        coef0=cfg.coef0, sigma=cfg.sigma, m_steps=m_steps, k_steps=k_steps)

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda j, i, k: (i, k)),
            pl.BlockSpec((br_, bk_), lambda j, i, k: (j, k)),
            pl.BlockSpec((c_, bm_), lambda j, i, k: (0, i)),
        ],
        out_specs=pl.BlockSpec((c_, br_), lambda j, i, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((c_, R), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bm_, br_), jnp.float32),     # kernel-tile acc
            pltpu.VMEM((c_, br_), jnp.float32),      # output acc
            pltpu.VMEM((bm_, 1), jnp.float32),       # RBF row sqnorms
            pltpu.VMEM((br_, 1), jnp.float32),       # RBF col sqnorms
        ],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(Ap, Bp, Xp)
    out = out[:c, :r]
    return out[0] if vec else out.T
