"""Where a compiled program's ops sit in its ``while`` loops.

Helpers for the structure tests that read optimized HLO text: which
computations a loop runs, and which of them run inside a loop that is
itself inside another (the s-step round's inner ``fori_loop`` inside the
round loop).
"""
from __future__ import annotations

import re

# custom-call targets of a dense factorization: LAPACK's on the CPU,
# the TPU compiler's own on a TPU
FACTORIZATION = re.compile(
    r'custom_call_target="([^"]*(?:getrf|potrf|Cholesky|LuDecomposition)'
    r'[^"]*)"')

_HEAD = re.compile(r"(?:ENTRY )?%(\S+) \(.*\{$")
_CALLEE = re.compile(r"(?:body|condition|calls|to_apply)=%([\w.\-]+)"
                     r"|branch_computations=\{([^}]*)\}")
_LOOP = re.compile(r"(?:body|condition)=%([\w.\-]+)")


def computations(hlo: str) -> dict:
    """{name: body lines} of every computation in optimized HLO."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = _HEAD.match(line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None and line != "}":
            comps[name].append(line)
    return comps


def _callees(lines):
    for line in lines:
        for m in _CALLEE.finditer(line):
            yield from ([m.group(1)] if m.group(1) else
                        [c.strip().lstrip("%")
                         for c in m.group(2).split(",")])


def _reachable(comps, roots):
    todo, seen = list(roots), set()
    while todo:
        c = todo.pop()
        if c not in seen and c in comps:
            seen.add(c)
            todo.extend(_callees(comps[c]))
    return seen


def _loops_in(comps, names):
    """Body and condition names of the ``while`` ops in ``names``."""
    return [c for n in names for line in comps[n] if " while(" in line
            for c in _LOOP.findall(line)]


def loop_computations(hlo: str, depth: int = 1) -> dict:
    """{name: lines} of the computations run at loop depth ``depth`` or
    deeper: 0 is the whole program, 1 anything a ``while`` runs, 2
    anything a ``while`` inside a ``while`` runs."""
    comps = computations(hlo)
    inside = set(comps)
    for _ in range(depth):
        inside = _reachable(comps, _loops_in(comps, inside))
    return {c: comps[c] for c in inside}


def factorizations(hlo: str, depth: int = 0) -> list:
    """The factorization custom-call targets at loop depth ``depth`` or
    deeper (``loop_computations``)."""
    return [t for lines in loop_computations(hlo, depth).values()
            for line in lines for t in FACTORIZATION.findall(line)]
