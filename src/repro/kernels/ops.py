"""Public jit'd wrappers for the Pallas kernels.

``gram`` dispatches to the fused Pallas kernel on TPU and to interpret mode
(Python-evaluated kernel body — bit-identical control flow) elsewhere, so
the same call sites run everywhere.  Pass ``force_ref=True`` to get the
pure-jnp oracle (used by tests and as the XLA-fusion baseline in §Perf).

``REPRO_SANITIZE=1`` in the environment forces interpret mode EVERYWHERE
(TPU included): the kernel bodies run under the Python evaluator, where
out-of-bounds block reads and NaN/Inf propagation are observable — the
runtime half of the ``repro.analysis`` sanitizer (the pytest fixture in
``tests/conftest.py`` adds ``jax_debug_nans``/``jax_debug_infs`` on top
for the kernel test modules).
"""
from __future__ import annotations

import os

import jax

from repro.core.kernels import (ExactGramOperator, KernelConfig,
                                StreamingGramOperator)
from .gram import gram_pallas
from .kmv import kmv_pallas
from .kmv_stream import kmv_stream_pallas
from .ref import gram_ref, kmv_ref


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def sanitize_mode() -> bool:
    return os.environ.get("REPRO_SANITIZE", "") == "1"


def _interpret(explicit=None) -> bool:
    if sanitize_mode():
        return True
    return (not on_tpu()) if explicit is None else explicit


def gram(A, B, cfg: KernelConfig, *, force_ref: bool = False, **tiles):
    if force_ref:
        return gram_ref(A, B, cfg)
    return gram_pallas(A, B, cfg, interpret=_interpret(), **tiles)


def kmv(A, B, X, cfg: KernelConfig, *, force_ref: bool = False, **tiles):
    """Fused ``K(A, B)^T X`` — the slab-free gram·matvec (DESIGN.md §2).
    Pallas on TPU, interpret mode elsewhere; ``force_ref`` materializes
    the slab (oracle / XLA-fusion baseline)."""
    if force_ref:
        return kmv_ref(A, B, X, cfg)
    return kmv_pallas(A, B, X, cfg, interpret=_interpret(), **tiles)


def kmv_stream(Xc, B, Xvc, cfg: KernelConfig, *, force_ref: bool = False,
               **kw):
    """Out-of-core ``K(A, B)^T X`` over CHUNKED data (DESIGN.md §14):
    the double-buffered DMA pipeline kernel on TPU, interpret mode
    elsewhere; ``force_ref`` flattens the chunks and materializes the
    slab (oracle)."""
    if force_ref:
        nc, cr, n = Xc.shape
        return kmv_ref(Xc.reshape(nc * cr, n), B,
                       Xvc.reshape(nc * cr, -1), cfg)
    return kmv_stream_pallas(Xc, B, Xvc, cfg, interpret=_interpret(), **kw)


def make_streaming_op_factory(chunk_rows: int, use_pallas: bool = True,
                              interpret=None):
    """op_factory for out-of-core solves: a ``StreamingGramOperator``
    whose streamed contraction runs the double-buffered DMA Pallas
    kernel (``kernels/kmv_stream.py``) — chunk i+1 copies in while
    chunk i contracts, so neither X nor any m-tall slab is ever
    VMEM/HBM-working-set resident.  ``use_pallas=False`` keeps the
    lax.scan fallback (the facade's default off-TPU)."""
    impl = None
    if use_pallas:
        interp = _interpret(interpret)

        def impl(Xc, B, Xvc, cfg):
            return kmv_stream_pallas(Xc, B, Xvc, cfg,
                                     interpret=interp).astype(Xvc.dtype)

    def factory(A, cfg):
        return StreamingGramOperator.from_dense(A, cfg,
                                                chunk_rows=chunk_rows,
                                                matvec_impl=impl)

    return factory


def sdpa_flash(q, k, v, causal=True, interpret=None, bq=256, bk=256):
    """Flash attention on (B, S, H, hd)-layout tensors (model convention).
    Returns (B, S, H, hdv).  K/V must already be head-repeated (GQA)."""
    from .flash_attention import flash_attention
    B, S, H, hd = q.shape
    T = k.shape[1]
    hdv = v.shape[-1]
    interp = _interpret(interpret)
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, T, hdv)
    o = flash_attention(qt, kt, vt, causal, None, bq, bk, interp)
    return o.reshape(B, H, S, hdv).transpose(0, 2, 1, 3)


def make_solver_op_factory(use_pallas: bool = True, interpret=None,
                           **tiles):
    """op_factory for the core solvers: a slab-free ``GramOperator`` whose
    matvec runs the fused Pallas KMV kernel — the m x sb slab never
    touches HBM.  Returns None (= jnp slab-free default) when
    ``use_pallas`` is False."""
    if not use_pallas:
        return None
    interp = _interpret(interpret)

    def matvec_impl(A, B, X, cfg):
        return kmv_pallas(A, B, X, cfg, interpret=interp,
                          **tiles).astype(X.dtype)

    def factory(A, cfg):
        return ExactGramOperator(A, cfg, matvec_impl=matvec_impl)

    return factory


def rmsnorm(x, scale, eps: float = 1e-6, interpret=None):
    """Fused RMSNorm (TPU Pallas; interpret-mode elsewhere)."""
    from .rmsnorm import rmsnorm_pallas
    return rmsnorm_pallas(x, scale, eps=eps, interpret=_interpret(interpret))
