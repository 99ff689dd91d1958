"""Names of the round's phases in the compiled program (DESIGN.md §15).

Each phase of a solver round runs under ``jax.named_scope(<name>)``
where the work is (the operators, the inner solves, the loop's
branches), so every driver inherits it.  A named scope is op metadata:
it costs nothing at run time and the program is the same with
telemetry on or off.  The compiled text carries each name in its
``op_name`` metadata; a device trace carries it in each operation's
``tf_op`` path, where the benchmark's readers find it.

An operation belongs to the innermost of these names on its path.
"""
from __future__ import annotations

import functools
import re

import jax

KMV = "kmv"                            # U^T X: operators' matvec
KMV_APPLY = "kmv_apply"                # K[:, idx] @ w: guarded f update
CROSS_BLOCK = "cross_block"            # the sampled (sb, sb) block
RECURRENCE = "recurrence"              # the s sequential local solves
BLOCK_SOLVE = "block_solve"            # K-RR's b x b linear solve
SCATTER = "scatter"                    # alpha[idx] += (and f +=)
PSUM = "psum"                          # the distributed collectives
METRIC_CHECK = "metric_check"          # the tolerance-check branch
DRIFT_CORRECTION = "drift_correction"  # the guard's residual replacement

SCOPES = (KMV, KMV_APPLY, CROSS_BLOCK, RECURRENCE, BLOCK_SOLVE, SCATTER,
          PSUM, METRIC_CHECK, DRIFT_CORRECTION)


_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")


def _unwrapped(part: str) -> str:
    """``vmap(kmv)`` -> ``kmv``: a transformation wraps the first scope
    under it; ``jit(f)`` is a function, not a scope, and stays."""
    m = _WRAPPED.match(part)
    while m and m.group(1) != "jit":
        part = m.group(2)
        m = _WRAPPED.match(part)
    return part


def owner(path: str):
    """The innermost scope on an op's name path, or None.

    ``path`` is an ``op_name`` (``jit(f)/while/body/kmv/dot_general``)
    or a trace's ``tf_op`` (the same with ``:<type>`` appended).  The
    last component is the operation itself, never a scope: a ``scatter``
    primitive outside the ``scatter`` scope owns nothing."""
    for part in reversed(path.split("/")[:-1]):
        part = _unwrapped(part)
        if part in SCOPES:
            return part
    return None


def scoped(name: str):
    """Decorator: run the function under ``jax.named_scope(name)``, a
    fresh scope object per call (one shared object is not reentrant)."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; expected one of "
                         f"{SCOPES}")

    def deco(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return deco
