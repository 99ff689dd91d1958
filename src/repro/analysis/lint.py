"""Jit-hygiene lint over ``src/repro/`` (DESIGN.md §11).

* CHK-TRACER (error) — Python-level branching (``if``/``while``/
  ternary) or host coercion (``bool()``/``float()``/``int()``) on a
  potentially-traced value inside a ROUND-FN closure (a function named
  ``round_fn`` or anything nested in a ``make_*round_fn`` factory).
  Round fns run under ``lax.scan``: host branching on a tracer raises
  ``TracerBoolConversionError`` at best and silently bakes in the
  trace-time value at worst.  Statically-safe tests are whitelisted:
  ``is``/``is not`` identity checks (the closure-wiring ``op is
  None`` pattern), comparisons whose subject is static array metadata
  (``.shape``/``.ndim``/``.dtype``/``.size``/``.name``), ``len()``,
  ``isinstance()``, and constants.
* CHK-PYTREE (error) — a dataclass carrying ``jnp.ndarray``-annotated
  fields that is NOT a registered pytree node: passing it across a jit
  boundary either fails or (as a static arg) hashes by object identity
  and retraces per instance.  NamedTuples are pytrees automatically
  and are skipped.
* CHK-STATIC (info) — ``static_argnames`` entries with Callable-typed
  parameters: jit caches by the callable's hash, so every lambda or
  local closure passed there silently recompiles.  Legitimate for
  module-level-function plumbing — suppress with the justification.
"""
from __future__ import annotations

import ast
import dataclasses as _dc
import importlib
import inspect
import os
import pkgutil
from typing import List, Optional, Tuple

from .findings import ERROR, INFO, Finding

SRC_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "name"}
_HOST_COERCIONS = {"bool", "float", "int"}


# ------------------------------------------------------- CHK-TRACER -----

def _is_static_expr(node: ast.expr) -> bool:
    """Conservatively: does this expression evaluate to a host value
    even when closure variables are tracers?"""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Attribute):
        return node.attr in _STATIC_ATTRS
    if isinstance(node, ast.Subscript):
        return _is_static_expr(node.value)          # x.shape[0]
    if isinstance(node, ast.Compare):
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            return True
        # a comparison is host-valued only when its SUBJECT is static
        # metadata (x.shape[0] == n); tracer == constant is a tracer
        return _is_static_expr(node.left)
    if isinstance(node, ast.BoolOp):
        return all(_is_static_expr(v) for v in node.values)
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Not):
        return _is_static_expr(node.operand)
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
        return node.func.id in ("len", "isinstance", "hasattr")
    if isinstance(node, ast.BinOp):
        return _is_static_expr(node.left) and _is_static_expr(node.right)
    return False


def _round_fn_nodes(tree: ast.AST):
    """Every function that is a round fn or lives inside a round-fn
    factory — the bodies ``lax.scan`` traces."""
    factories = [n for n in ast.walk(tree)
                 if isinstance(n, ast.FunctionDef)
                 and n.name.startswith("make_") and "round_fn" in n.name]
    seen = set()
    for fac in factories:
        for n in ast.walk(fac):
            if isinstance(n, ast.FunctionDef) and n is not fac:
                seen.add(id(n))
                yield n
    for n in ast.walk(tree):
        if isinstance(n, ast.FunctionDef) and n.name == "round_fn" \
                and id(n) not in seen:
            yield n


def _check_tracer(path: str, tree: ast.AST) -> List[Finding]:
    out = []
    for fn in _round_fn_nodes(tree):
        for node in ast.walk(fn):
            if isinstance(node, (ast.If, ast.While, ast.IfExp)):
                test, what = node.test, type(node).__name__.lower()
            elif (isinstance(node, ast.Call)
                  and isinstance(node.func, ast.Name)
                  and node.func.id in _HOST_COERCIONS and node.args):
                test, what = node.args[0], f"{node.func.id}()"
            else:
                continue
            if not _is_static_expr(test):
                out.append(Finding(
                    "CHK-TRACER", ERROR, path, node.lineno,
                    f"host-side {what} on a potentially traced value "
                    f"inside round fn '{fn.name}' — round fns run under "
                    f"lax.scan; use jnp.where/lax.cond or hoist the "
                    f"branch out of the traced closure"))
    return out


# ------------------------------------------------------- CHK-PYTREE -----

def _registered_pytree(cls) -> Optional[bool]:
    """True/False if the jax registry is inspectable, None if the
    private registry moved (then the check abstains rather than lies)."""
    try:
        from jax._src.tree_util import _registry
        return cls in _registry
    except Exception:
        return None


def _array_fields(cls) -> List[str]:
    names = []
    for f in _dc.fields(cls):
        ann = f.type if isinstance(f.type, str) else getattr(
            f.type, "__name__", str(f.type))
        if "ndarray" in ann or "Array" in ann:
            names.append(f.name)
    return names


def iter_repro_dataclasses():
    """Every dataclass DEFINED in a ``repro`` module (imports every
    submodule; they are all import-safe by the tier-1 suite)."""
    import repro
    for info in pkgutil.walk_packages(repro.__path__, "repro."):
        try:
            mod = importlib.import_module(info.name)
        except Exception:
            continue
        for obj in vars(mod).values():
            if (inspect.isclass(obj) and obj.__module__ == info.name
                    and _dc.is_dataclass(obj)
                    and not issubclass(obj, tuple)):
                yield mod, obj


def _check_pytree() -> List[Finding]:
    out = []
    seen = set()
    for mod, cls in iter_repro_dataclasses():
        if cls in seen:
            continue
        seen.add(cls)
        arrays = _array_fields(cls)
        if not arrays or _registered_pytree(cls) in (True, None):
            continue
        try:
            path = inspect.getsourcefile(cls)
            line = inspect.getsourcelines(cls)[1]
        except (OSError, TypeError):
            path, line = getattr(mod, "__file__", "<unknown>"), 1
        out.append(Finding(
            "CHK-PYTREE", ERROR, os.path.abspath(path), line,
            f"dataclass {cls.__name__} carries array fields "
            f"{arrays} but is not a registered pytree node — it "
            f"cannot cross a jit boundary (register via "
            f"jax.tree_util.register_dataclass, or suppress if it is "
            f"host-side only)"))
    return out


# ------------------------------------------------------- CHK-STATIC -----

def _static_argnames(dec: ast.expr) -> Optional[Tuple[int, List[str]]]:
    """(lineno, names) if ``dec`` is a partial(jax.jit, static_argnames=
    (...)) / jax.jit(static_argnames=...) decorator with literal names."""
    if not isinstance(dec, ast.Call):
        return None
    src = ast.unparse(dec.func)
    if not (src.endswith("partial") or src.endswith("jit")):
        return None
    if src.endswith("partial") and not any(
            "jit" in ast.unparse(a) for a in dec.args):
        return None
    for kw in dec.keywords:
        if kw.arg == "static_argnames":
            try:
                names = ast.literal_eval(kw.value)
            except ValueError:
                return None
            if isinstance(names, str):
                names = [names]
            return dec.lineno, list(names)
    return None


def _check_static(path: str, tree: ast.AST) -> List[Finding]:
    out = []
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.FunctionDef):
            continue
        for dec in fn.decorator_list:
            hit = _static_argnames(dec)
            if hit is None:
                continue
            line, names = hit
            callables = []
            for arg in fn.args.args + fn.args.kwonlyargs:
                if arg.arg in names and arg.annotation is not None \
                        and "Callable" in ast.unparse(arg.annotation):
                    callables.append(arg.arg)
            if callables:
                out.append(Finding(
                    "CHK-STATIC", INFO, path, line,
                    f"{fn.name}: Callable-typed static argnames "
                    f"{callables} — jit caches on callable identity, so "
                    f"each distinct closure retraces; pass module-level "
                    f"functions only (or suppress with the reason)"))
    return out


# ------------------------------------------------------------- entry -----

def run(root: str = SRC_ROOT) -> List[Finding]:
    findings: List[Finding] = []
    for dirpath, _dirs, files in os.walk(root):
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            path = os.path.abspath(os.path.join(dirpath, fname))
            with open(path, encoding="utf-8") as fh:
                tree = ast.parse(fh.read(), filename=path)
            findings.extend(_check_tracer(path, tree))
            findings.extend(_check_static(path, tree))
    findings.extend(_check_pytree())
    return findings
