"""Pure-jnp oracles for every Pallas kernel in this package, and the
materialized-slab reference operator the solver tests compare against."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.kernels import KernelConfig, gram_slab


class SlabGramOperator:
    """Reference ``GramOperator`` that MATERIALIZES the m x r slab
    ``U = gram(A, A[idx])`` every round: ``round_data`` returns the
    sampled block ``U[idx]`` and ``U^T x`` from it.  The tests pass it as
    a solver's ``op_factory`` (``partial(SlabGramOperator, gram=...)``
    swaps in another gram, e.g. the Pallas one) to check the slab-free
    operators against the paper's formulation.  Like
    ``AllreduceGramOperator`` it implements only ``round_data``."""

    def __init__(self, A, cfg: KernelConfig, gram=gram_slab):
        self.A, self.cfg, self.gram = A, cfg, gram

    def for_rounds(self):
        return self

    def round_data(self, idx, x):
        U = self.gram(self.A, self.A[idx], self.cfg)
        return U[idx, :], U.T @ x


def gram_ref(A: jnp.ndarray, B: jnp.ndarray, cfg: KernelConfig,
             out_dtype=jnp.float32) -> jnp.ndarray:
    """Oracle for kernels/gram.py: epilogue(A @ B^T) in f32 accumulation."""
    return gram_slab(A.astype(jnp.float32), B.astype(jnp.float32),
                     cfg).astype(out_dtype)


def kmv_ref(A: jnp.ndarray, B: jnp.ndarray, X: jnp.ndarray,
            cfg: KernelConfig, out_dtype=jnp.float32) -> jnp.ndarray:
    """Oracle for kernels/kmv.py: ``K(A, B)^T X`` with the slab
    materialized in f32 (the thing the fused kernel must never do)."""
    U = gram_slab(A.astype(jnp.float32), B.astype(jnp.float32), cfg)
    return (U.T @ X.astype(jnp.float32)).astype(out_dtype)


def flash_attention_ref(q, k, v, causal=True, scale=None):
    """Oracle for kernels/flash_attention.py.  q/k/v: (BH, S|T, hd)."""
    hd = q.shape[-1]
    scale = scale if scale is not None else hd ** -0.5
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        S, T = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((S, T), bool))
        s = jnp.where(mask, s, -1e30)
    p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
    p = p / jnp.sum(p, -1, keepdims=True)
    out = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
