"""Streaming (out-of-core) KMV Pallas kernel: double-buffered DMA.

Computes ``U^T X`` with ``U = K(A, B)`` for an A that does NOT live in
fast device memory: A arrives pre-chunked as ``Xc: (nc, cr, n)`` row
blocks resident in HBM/host (``MemorySpace.ANY`` — the pipelined
BlockSpec machinery never touches it), together with the equally chunked
right-hand side ``Xvc: (nc, cr, c)``.  The kernel owns TWO VMEM slots
per stream and overlaps the DMA of chunk ``i+1`` with the contraction of
chunk ``i`` — the flash-attention double-buffering idiom
(``kernels/flash_attention.py``), written out with manual
``make_async_copy``/semaphore pairs because the chunk axis is a data
axis, not a grid axis:

    warm-up: start DMA of chunk 0 into slot 0
    loop i:  start DMA of chunk i+1 into slot (i+1)%2   (prefetch)
             wait  DMA of chunk i   in   slot i%2       (consume)
             dots  = chunk_i @ B^T          (MXU)
             ktile = epilogue(dots)         (VPU, Table-1 kernel)
             acc  += ktile^T @ x_i          (MXU)

Steady state the pipe pays ``max(t_dma, t_compute)`` per chunk instead
of the sum — ``core.perf_model.stream_pipeline_cost`` prices exactly
this overlap, and ``repro.analysis``'s CHK-DMA check statically verifies
the wait-before-read and slot-alternation invariants of this loop.

Zero-padding is contraction-safe exactly as in ``kmv.kmv_pallas``: the
tail chunk's padded rows carry zero ``x`` rows, so their (nonzero for
RBF/poly) kernel values contribute nothing, and padded B columns are
sliced off by the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.kernels import LINEAR, POLYNOMIAL, RBF, KernelConfig
from .gram import _pad_to, _round_up, _sublane


def _kmv_stream_kernel(xc_hbm, xvc_hbm, b_ref, o_ref, *,
                       kernel_name: str, degree: int, coef0: float,
                       sigma: float, nc: int):
    """xc_hbm: (nc, cr, n) ANY, xvc_hbm: (nc, cr, c) ANY,
    b_ref: (r, n) VMEM, o_ref: (r, c) VMEM."""
    cr, n = xc_hbm.shape[1], xc_hbm.shape[2]
    c = xvc_hbm.shape[2]

    def body(a_buf, x_buf, a_sem, x_sem, acc):
        bt = b_ref[...].astype(jnp.float32)              # (r, n)
        if kernel_name == RBF:
            cs = jnp.sum(bt * bt, axis=1)                # (r,)
        # warm-up: fill slot 0 while the loop below sets up
        pltpu.make_async_copy(xc_hbm.at[0], a_buf.at[0],
                              a_sem.at[0]).start()
        pltpu.make_async_copy(xvc_hbm.at[0], x_buf.at[0],
                              x_sem.at[0]).start()
        acc[...] = jnp.zeros_like(acc)

        def loop(i, _):
            slot = jax.lax.rem(i, 2)
            nxt = jax.lax.rem(i + 1, 2)

            @pl.when(i + 1 < nc)
            def _prefetch():                 # DMA chunk i+1 into the
                pltpu.make_async_copy(       # OTHER slot while chunk i
                    xc_hbm.at[i + 1], a_buf.at[nxt],      # computes
                    a_sem.at[nxt]).start()
                pltpu.make_async_copy(
                    xvc_hbm.at[i + 1], x_buf.at[nxt],
                    x_sem.at[nxt]).start()

            pltpu.make_async_copy(xc_hbm.at[i], a_buf.at[slot],
                                  a_sem.at[slot]).wait()
            pltpu.make_async_copy(xvc_hbm.at[i], x_buf.at[slot],
                                  x_sem.at[slot]).wait()
            a = a_buf[slot].astype(jnp.float32)          # (cr, n)
            x = x_buf[slot].astype(jnp.float32)          # (cr, c)
            dots = jax.lax.dot_general(
                a, bt, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)      # (cr, r) MXU
            if kernel_name == LINEAR:
                ktile = dots
            elif kernel_name == POLYNOMIAL:
                ktile = (coef0 + dots) ** degree
            else:                                        # RBF
                rs = jnp.sum(a * a, axis=1)              # (cr,)
                sq = rs[:, None] + cs[None, :] - 2.0 * dots
                ktile = jnp.exp(-sigma * jnp.maximum(sq, 0.0))
            acc[...] += jax.lax.dot_general(
                ktile, x, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)      # (r, c) MXU

        jax.lax.fori_loop(0, nc, loop, None)
        o_ref[...] = acc[...].astype(o_ref.dtype)

    pl.run_scoped(
        body,
        a_buf=pltpu.VMEM((2, cr, n), xc_hbm.dtype),
        x_buf=pltpu.VMEM((2, cr, c), xvc_hbm.dtype),
        a_sem=pltpu.SemaphoreType.DMA((2,)),
        x_sem=pltpu.SemaphoreType.DMA((2,)),
        acc=pltpu.VMEM(o_ref.shape, jnp.float32))


@functools.partial(jax.jit, static_argnames=("cfg", "interpret",
                                             "out_dtype"))
def kmv_stream_pallas(Xc: jnp.ndarray, B: jnp.ndarray, Xvc: jnp.ndarray,
                      cfg: KernelConfig, *, interpret: bool = False,
                      out_dtype=jnp.float32):
    """``U^T X`` for ``U = K(A, B)`` with A CHUNKED out-of-core.

    Xc: (nc, cr, n) chunked rows of A (zero-padded tail), Xvc:
    (nc, cr, c) the identically chunked right-hand side, B: (r, n).
    Returns (r, c) in ``out_dtype``.  Shapes need not be aligned —
    chunk rows, features, r and c are zero-padded (contraction-safe,
    module docstring) and the output is sliced back.
    """
    nc, cr, n = Xc.shape
    r, n2 = B.shape
    nc2, cr2, c = Xvc.shape
    assert n == n2 and nc == nc2 and cr == cr2, (Xc.shape, B.shape,
                                                 Xvc.shape)
    sub = max(_sublane(Xc.dtype), _sublane(Xvc.dtype))
    cr_ = _round_up(cr, sub)
    n_ = _round_up(n, 128)
    r_ = _round_up(r, sub)
    c_ = _round_up(c, 128)

    Xp = _pad_to(_pad_to(Xc, cr_, 1), n_, 2)
    Bp = _pad_to(_pad_to(B, r_, 0), n_, 1)
    Vp = _pad_to(_pad_to(Xvc, cr_, 1), c_, 2)

    kern = functools.partial(
        _kmv_stream_kernel, kernel_name=cfg.name, degree=cfg.degree,
        coef0=cfg.coef0, sigma=cfg.sigma, nc=nc)

    out = pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec((r_, n_), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((r_, c_), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((r_, c_), out_dtype),
        interpret=interpret,
    )(Xp, Vp, Bp)
    return out[:r, :c]
