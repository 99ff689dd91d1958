"""Compile the main path's Pallas kernels for a TPU v5e, without a chip.

The TPU compiler ships with jax and compiles for a chip that is
described, not attached (``jax.experimental.topologies``).  These
compiles catch what the interpret-mode tests cannot: a slice the
tiling refuses, or a kernel that asks for more VMEM than it may use.
Shapes are the deployment's: m=2^18 rows, n=256 features, r=128 sampled
rows (s*b = 16*8).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library (a second one fails on its
lock file), and every test worker imports this file.  Keep these tests
in this one file, so that one worker loads the library for all of them.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from hlo_loops import factorizations, loop_computations
from repro.core.kernels import KernelConfig
from repro.core.perf_model import (STREAM_CHUNK_CANDIDATES,
                                   choose_chunk_rows, stream_chunk_fits)
from repro.kernels.gram import gram_pallas
from repro.kernels.kmv import kmv_pallas
from repro.kernels.kmv_stream import kmv_stream_pallas

M, N, R = 2 ** 18, 256, 128
KERNELS = ("linear", "polynomial", "rbf")


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile
    cache off around the compiles (a described-device compile is written
    to the cache but cannot be read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _shape(sharding, *dims):
    return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=sharding)


def _compile_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel was not lowered"


@pytest.mark.parametrize("kernel", KERNELS)
def test_kmv_pallas_compiles_for_v5e(one_chip, kernel):
    cfg = KernelConfig(kernel)
    _compile_kernel(lambda A, B, X: kmv_pallas(A, B, X, cfg),
                    _shape(one_chip, M, N), _shape(one_chip, R, N),
                    _shape(one_chip, M))


def test_gram_pallas_compiles_for_v5e(one_chip):
    cfg = KernelConfig("rbf")
    _compile_kernel(lambda A, B: gram_pallas(A, B, cfg),
                    _shape(one_chip, M, N), _shape(one_chip, R, N))


def _largest_feasible_chunk():
    return max(cr for cr in STREAM_CHUNK_CANDIDATES
               if stream_chunk_fits(cr, N, R))


def _compile_stream(sharding, chunk_rows):
    cfg = KernelConfig("rbf")
    nc = M // chunk_rows
    _compile_kernel(lambda Xc, B, V: kmv_stream_pallas(Xc, B, V, cfg),
                    _shape(sharding, nc, chunk_rows, N),
                    _shape(sharding, R, N),
                    _shape(sharding, nc, chunk_rows, 1))


@pytest.mark.parametrize("pick", ["autotuned", "largest_feasible"])
def test_kmv_stream_pallas_compiles_for_v5e(one_chip, pick):
    """The chunk ``choose_chunk_rows`` picks, and the largest it calls
    feasible, both compile: the working-set model admits nothing the
    compiler refuses."""
    chunk = (choose_chunk_rows(M, N, R, "rbf") if pick == "autotuned"
             else _largest_feasible_chunk())
    _compile_stream(one_chip, chunk)


def test_kmv_stream_pallas_refused_above_model_budget(one_chip):
    """The next candidate above the largest feasible chunk is refused by
    the compiler for VMEM, as the working-set model predicts."""
    above = min(cr for cr in STREAM_CHUNK_CANDIDATES
                if cr > _largest_feasible_chunk())
    assert not stream_chunk_fits(above, N, R)
    with pytest.raises(Exception, match="vmem"):
        _compile_stream(one_chip, above)


# --- the s-step fit's round loop at LIBSVM covtype's shape ----------------

def test_sstep_fit_round_pads_no_a_for_v5e(one_chip):
    """K-SVM, rbf, s = 16 at 581,012 x 54: the loop-ready operator pads
    A to whole 2,048-row KMV blocks once, before the round loop, and no
    round pads an array of A's size again."""
    from repro.core import SVMConfig, sstep_dcd_ksvm
    from repro.core.kernels import ExactGramOperator

    m, n, H = 581_012, 54, 16_384
    cfg = SVMConfig(C=1.0, loss="l1", kernel=KernelConfig("rbf"))
    hlo = jax.jit(lambda A, y, a0, sched, op: sstep_dcd_ksvm(
        A, y, a0, sched, cfg, 16, op=op)[0]).lower(
        _shape(one_chip, m, n), _shape(one_chip, m), _shape(one_chip, m),
        jax.ShapeDtypeStruct((H,), jnp.int32, sharding=one_chip),
        ExactGramOperator(_shape(one_chip, m, n), cfg.kernel)
    ).compile().as_text()
    a_pad = re.compile(r"^\s*(%\S+) = f32\[([\d,]+)\]\S* pad\(")

    def pads(lines):
        return [name for line in lines for name, dims in a_pad.findall(line)
                if np.prod([int(d) for d in dims.split(",")]) >= m * n]

    in_loop = pads(line for lines in loop_computations(hlo).values()
                   for line in lines)
    assert not in_loop, in_loop
    assert len(pads(hlo.splitlines())) == 1


def test_sstep_bdcd_round_factors_outside_inner_loop_for_v5e(one_chip):
    """K-RR, rbf, s = 16, b = 8 at YearPredictionMSD's 463,715 x 90: the
    round factors its 16 diagonal 8 x 8 blocks in one batched call before
    the eq. (3) steps, so the inner loop's ``while`` holds no LU or
    Cholesky."""
    from repro.core import KRRConfig, sstep_bdcd_krr
    from repro.core.kernels import ExactGramOperator

    m, n, H, s, b = 463_715, 90, 16_384, 16, 8
    cfg = KRRConfig(lam=0.1, kernel=KernelConfig("rbf"))
    hlo = jax.jit(lambda A, y, a0, sched, op: sstep_bdcd_krr(
        A, y, a0, sched, cfg, s, op=op)[0]).lower(
        _shape(one_chip, m, n), _shape(one_chip, m), _shape(one_chip, m),
        jax.ShapeDtypeStruct((H, b), jnp.int32, sharding=one_chip),
        ExactGramOperator(_shape(one_chip, m, n), cfg.kernel)
    ).compile().as_text()
    assert loop_computations(hlo, 2), "no inner loop compiled"
    assert len(factorizations(hlo, 1)) == 1, factorizations(hlo)
    assert not factorizations(hlo, 2), factorizations(hlo, 2)
    assert factorizations(hlo) == factorizations(hlo, 1)
