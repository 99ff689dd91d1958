"""Pallas call-site capture and the kernel entry-point registry.

The sanitizer (``pallas_check``) needs every ``pl.pallas_call`` in the
tree with a CONCRETE grid and BlockSpecs — index maps are Python
lambdas over runtime-derived block counts, so they cannot be inspected
from source alone.  Two mechanisms cooperate:

* ``discover_sites`` AST-walks ``src/repro/kernels/`` for the
  ``pl.pallas_call`` call expressions — the ground truth of what exists.
* ``capture`` monkeypatches ``jax.experimental.pallas.pallas_call``
  with a recorder that snapshots (grid, specs, out_shape, scratch,
  dimension_semantics, caller file/line) and returns a stub runner
  producing zeros — so driving a kernel's UNJITTED entry point (via
  ``__wrapped__``, bypassing the jit cache) records its launch without
  compiling or executing anything.

``ENTRY_POINTS`` registers one representative concretization per public
kernel entry point.  To register a new kernel, append an ``EntryPoint``
whose thunk calls the new wrapper with shapes exercising every padding
branch (non-block-aligned dims, both dtypes if the kernel is
dtype-generic); ``pallas_check`` cross-references captured (file, line)
pairs against ``discover_sites`` and flags unexercised sites (CHK-SITE)
so a forgotten registration is itself a finding.
"""
from __future__ import annotations

import ast
import contextlib
import dataclasses
import inspect
import math
import os
from typing import Callable, List, Optional, Sequence, Tuple

import jax.numpy as jnp
from jax.experimental import pallas as _pallas_mod

KERNELS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "kernels")


@dataclasses.dataclass
class SpecInfo:
    """One BlockSpec, concretized: ``block_shape`` (ints), ``index_map``
    (the live lambda), the shape/dtype of the array it blocks, and the
    declared ``memory_space`` (None = default pipelined VMEM)."""

    block_shape: Tuple[int, ...]
    index_map: Optional[Callable]
    array_shape: Tuple[int, ...]
    dtype: object
    memory_space: object = None

    @property
    def is_any_space(self) -> bool:
        """True for ``MemorySpace.ANY`` specs: the array stays in
        HBM/host and the BlockSpec pipeline never stages it through
        VMEM (the kernel DMAs slices itself) — such inputs must not be
        priced against the VMEM block budget."""
        ms = self.memory_space
        return ms is not None and "any" in str(ms).lower()


@dataclasses.dataclass
class CapturedCall:
    """One recorded ``pl.pallas_call`` launch."""

    path: str
    function: str
    line: int
    grid: Tuple[int, ...]
    in_specs: List[SpecInfo]
    out_specs: List[SpecInfo]
    scratch_bytes: int
    dimension_semantics: Optional[Tuple[str, ...]]
    entry: str = ""

    @property
    def site(self) -> Tuple[str, int]:
        return (self.path, self.line)

    def block_bytes(self) -> int:
        """Per-grid-step VMEM block bytes (in + out blocks).  ANY-space
        specs are excluded: those arrays never transit the BlockSpec
        pipeline (the kernel's own scratch + DMA slots, counted in
        ``scratch_bytes``, are their VMEM footprint)."""
        return sum(
            math.prod(s.block_shape) * jnp.dtype(s.dtype).itemsize
            for s in self.in_specs + self.out_specs
            if not s.is_any_space)


def _as_list(x) -> list:
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _spec_infos(specs, arrays) -> List[SpecInfo]:
    out = []
    for spec, arr in zip(_as_list(specs), arrays):
        shape = tuple(jnp.shape(arr)) if not hasattr(arr, "shape") \
            else tuple(arr.shape)
        dtype = getattr(arr, "dtype", jnp.float32)
        block = getattr(spec, "block_shape", None)
        block = tuple(block) if block is not None else shape
        out.append(SpecInfo(block, getattr(spec, "index_map", None),
                            shape, dtype,
                            getattr(spec, "memory_space", None)))
    return out


def _scratch_bytes(scratch_shapes) -> int:
    total = 0
    for s in _as_list(scratch_shapes):
        shape = getattr(s, "shape", None)
        dtype = getattr(s, "dtype", None)
        if shape is not None and dtype is not None:
            total += math.prod(shape) * jnp.dtype(dtype).itemsize
    return total


@contextlib.contextmanager
def capture():
    """Swap ``pallas.pallas_call`` for a recorder; yields the list that
    accumulates ``CapturedCall`` rows.  The stub runner returns zeros of
    ``out_shape`` so wrapper code after the launch (slicing, reshape)
    still executes — drive only UNJITTED entry points under this, or
    the jit cache will skip the patched call."""
    calls: List[CapturedCall] = []
    real = _pallas_mod.pallas_call

    def fake(kernel, *, grid=None, in_specs=None, out_specs=None,
             out_shape=None, scratch_shapes=(), compiler_params=None,
             interpret=False, **kw):
        frame = inspect.currentframe().f_back
        site = (os.path.abspath(frame.f_code.co_filename),
                frame.f_code.co_name, frame.f_lineno)
        sem = getattr(compiler_params, "dimension_semantics", None)
        shapes = _as_list(out_shape)
        grid_t = tuple(grid) if isinstance(grid, (list, tuple)) else (grid,)

        def runner(*args):
            rec = CapturedCall(
                path=site[0],
                function=site[1],
                line=site[2],
                grid=tuple(int(g) for g in grid_t),
                in_specs=_spec_infos(in_specs, args),
                out_specs=_spec_infos(out_specs, shapes),
                scratch_bytes=_scratch_bytes(scratch_shapes),
                dimension_semantics=tuple(sem) if sem else None,
            )
            calls.append(rec)
            outs = [jnp.zeros(s.shape, s.dtype) for s in shapes]
            return outs if isinstance(out_shape, (list, tuple)) else outs[0]

        return runner

    _pallas_mod.pallas_call = fake
    try:
        yield calls
    finally:
        _pallas_mod.pallas_call = real


def _unwrap(fn):
    return getattr(fn, "__wrapped__", fn)


@dataclasses.dataclass(frozen=True)
class EntryPoint:
    """A registered kernel concretization: ``run`` drives the unjitted
    wrapper under ``capture`` with representative non-aligned shapes."""

    name: str
    run: Callable[[], None]


def _run_gram(dtype):
    def go():
        from repro.core.kernels import KernelConfig
        from repro.kernels import gram
        A = jnp.zeros((200, 700), dtype)
        B = jnp.zeros((136, 700), dtype)
        _unwrap(gram.gram_pallas)(A, B, KernelConfig(name="rbf"))
    return go


def _run_kmv(kernel_name, vec):
    def go():
        from repro.core.kernels import KernelConfig
        from repro.kernels import kmv
        A = jnp.zeros((200, 700), jnp.float32)
        B = jnp.zeros((136, 700), jnp.float32)
        X = jnp.zeros((200,) if vec else (200, 5), jnp.float32)
        _unwrap(kmv.kmv_pallas)(A, B, X, KernelConfig(name=kernel_name))
    return go


def _run_kmv_stream(kernel_name, c):
    def go():
        from repro.core.kernels import KernelConfig
        from repro.kernels import kmv_stream
        Xc = jnp.zeros((4, 24, 70), jnp.float32)    # ragged: 24 % 8,
        B = jnp.zeros((12, 70), jnp.float32)        # 70 % 128, 12 % 8
        Xvc = jnp.zeros((4, 24, c), jnp.float32)
        _unwrap(kmv_stream.kmv_stream_pallas)(
            Xc, B, Xvc, KernelConfig(name=kernel_name))
    return go


def _run_flash():
    from repro.kernels import flash_attention as fa
    BH, S, hd = 2, 512, 128
    q = jnp.zeros((BH, S, hd), jnp.float32)
    o, lse = _unwrap(fa.flash_fwd)(q, q, q, causal=True)
    _unwrap(fa.flash_bwd)(q, q, q, o, lse, q, causal=True)


def _run_rmsnorm():
    from repro.kernels import rmsnorm
    x = jnp.zeros((520, 256), jnp.float32)
    _unwrap(rmsnorm.rmsnorm_pallas)(x, jnp.zeros((256,), jnp.float32))


ENTRY_POINTS: Tuple[EntryPoint, ...] = (
    EntryPoint("gram_pallas[f32,rbf]", _run_gram(jnp.float32)),
    EntryPoint("gram_pallas[bf16,rbf]", _run_gram(jnp.bfloat16)),
    EntryPoint("kmv_pallas[rbf,mat]", _run_kmv("rbf", vec=False)),
    EntryPoint("kmv_pallas[linear,vec]", _run_kmv("linear", vec=True)),
    EntryPoint("kmv_stream_pallas[rbf]", _run_kmv_stream("rbf", c=5)),
    EntryPoint("kmv_stream_pallas[linear]",
               _run_kmv_stream("linear", c=1)),
    EntryPoint("flash_attention[fwd+bwd]", _run_flash),
    EntryPoint("rmsnorm_pallas", _run_rmsnorm),
)


def capture_entry_points(entries: Sequence[EntryPoint] = ENTRY_POINTS
                         ) -> List[CapturedCall]:
    """Drive every registered entry point under ``capture``; each
    captured call is tagged with the entry name that produced it."""
    out: List[CapturedCall] = []
    for ep in entries:
        with capture() as calls:
            ep.run()
        for c in calls:
            c.entry = ep.name
        out.extend(calls)
    return out


def discover_sites(root: str = KERNELS_DIR) -> List[Tuple[str, int]]:
    """AST ground truth: every ``pallas_call`` call expression under
    ``root`` as (abspath, lineno) — matched against captured calls to
    flag unexercised sites."""
    sites = []
    for dirpath, _dirs, files in os.walk(root):
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            path = os.path.abspath(os.path.join(dirpath, fname))
            with open(path, encoding="utf-8") as fh:
                tree = ast.parse(fh.read(), filename=path)
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                fn = node.func
                name = fn.attr if isinstance(fn, ast.Attribute) else \
                    fn.id if isinstance(fn, ast.Name) else None
                if name == "pallas_call":
                    sites.append((path, node.lineno))
    return sites
