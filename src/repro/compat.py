"""Thin wrappers over the jax 0.9 APIs the repo calls from many sites.

The repo pins ``jax==0.9.0`` (``pyproject.toml``); these names keep the
call sites short and give each API one place to change on the next pin.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as _pltpu

CompilerParams = _pltpu.CompilerParams


def enable_x64(flag: bool = True):
    """Context manager turning 64-bit types on (or off) for its body."""
    return jax.enable_x64(flag)


def make_mesh_auto(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis explicitly ``AxisType.Auto``."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))


def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict (``{}`` when XLA has none)."""
    return compiled.cost_analysis() or {}


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False,
              axis_names=None):
    kw = {"check_vma": check_vma}
    if axis_names is not None:
        kw["axis_names"] = axis_names
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)
