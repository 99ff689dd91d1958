"""Tests for the ``repro.analysis`` static-analysis subsystem:
positive/negative fixtures per check, the suppression protocol, the
collective census, pytree round-trips, and the tier-1 comm-schedule
smoke (an extra psum or a broken s-step schedule fails here locally,
before CI)."""
import ast
import textwrap
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

import analysis_fixtures as fx
from repro.analysis import lint, pallas_check, registry, run_all
from repro.analysis.findings import ERROR, Finding, apply_suppressions
from repro.analysis import comm_check
from repro.compat import make_mesh_auto, shard_map
from repro.core.kernels import (ExactGramOperator, KernelConfig,
                                LowRankGramOperator)
from repro.core.nystrom import NystromMap
from repro.core.perf_model import setup_collectives
from repro.launch.jaxpr_analysis import (COLLECTIVE_PRIMS,
                                         collective_census,
                                         count_collective_executions)


def _pallas_findings(fixture):
    with registry.capture() as calls:
        fixture()
    return pallas_check.analyze_calls(calls)


# ------------------------------------------------ pallas sanitizer -----

@pytest.mark.parametrize("bad,good,check", [
    (fx.racing_out_spec, fx.accumulating_out_spec, "CHK-RACE"),
    (fx.coverage_hole, fx.full_coverage, "CHK-HOLE"),
    (fx.misaligned_block, fx.aligned_block, "CHK-ALIGN"),
    (fx.vmem_hog, fx.vmem_modest, "CHK-VMEM"),
], ids=["race", "hole", "align", "vmem"])
def test_pallas_positive_negative(bad, good, check):
    caught = _pallas_findings(bad)
    assert check in {f.check for f in caught}, caught
    assert {f.check for f in caught} <= {check}, \
        "fixture should trip exactly one check kind"
    assert _pallas_findings(good) == []


def test_real_kernels_all_captured_and_clean():
    calls = registry.capture_entry_points()
    covered = {c.site for c in calls}
    sites = set(registry.discover_sites())
    assert sites and sites <= covered, sites - covered
    assert pallas_check.run() == []


# ----------------------------------------------------- suppressions -----

def test_noqa_suppresses_with_justification():
    f = Finding("CHK-X", ERROR, "mem.py", 2, "boom")
    out = apply_suppressions(
        [f], {"mem.py": ["# repro: noqa[CHK-X] known benign", "code()"]})
    assert out[0].suppressed and out[0].justification == "known benign"


def test_noqa_without_justification_is_a_finding():
    f = Finding("CHK-X", ERROR, "mem.py", 2, "boom")
    out = apply_suppressions(
        [f], {"mem.py": ["# repro: noqa[CHK-X]", "code()"]})
    assert out[0].check == "CHK-NOQA" and not out[0].suppressed


def test_noqa_other_id_does_not_suppress():
    f = Finding("CHK-X", ERROR, "mem.py", 2, "boom")
    out = apply_suppressions(
        [f], {"mem.py": ["# repro: noqa[CHK-Y] wrong check", "code()"]})
    assert not out[0].suppressed and out[0].check == "CHK-X"


# -------------------------------------------------------- jit lint -----

def test_tracer_branch_caught():
    src = textwrap.dedent("""
        def make_foo_round_fn(A):
            def round_fn(alpha, xs):
                if alpha > 0:
                    alpha = -alpha
                return float(alpha)
            return round_fn
    """)
    found = lint._check_tracer("<fx>", ast.parse(src))
    assert len(found) == 2
    assert {f.check for f in found} == {"CHK-TRACER"}


def test_tracer_static_tests_allowed():
    src = textwrap.dedent("""
        def make_foo_round_fn(A, op_factory=None):
            def round_fn(alpha, xs):
                if op_factory is not None:
                    alpha = op_factory(alpha)
                if A.ndim == 2 and len(xs) > 1:
                    alpha = alpha + 1
                return alpha
            return round_fn
    """)
    assert lint._check_tracer("<fx>", ast.parse(src)) == []


def test_static_callable_argname_caught():
    src = textwrap.dedent("""
        @functools.partial(jax.jit, static_argnames=("op_factory",))
        def solve(A, op_factory: Optional[Callable] = None):
            return A
    """)
    found = lint._check_static("<fx>", ast.parse(src))
    assert [f.check for f in found] == ["CHK-STATIC"]


def test_static_non_callable_argname_clean():
    src = textwrap.dedent("""
        @functools.partial(jax.jit, static_argnames=("cfg",))
        def solve(A, cfg: KernelConfig = None):
            return A
    """)
    assert lint._check_static("<fx>", ast.parse(src)) == []


def test_lint_flags_known_host_records_only():
    found = lint.run()
    pytree = {f.message.split()[1] for f in found
              if f.check == "CHK-PYTREE"}
    # the host-side result records are flagged (and suppressed in-tree);
    # the registered operator containers must NOT appear
    assert "FitResult" in pytree
    assert pytree.isdisjoint({"ExactGramOperator", "LowRankGramOperator",
                              "NystromMap"})
    assert not any(f.check == "CHK-TRACER" for f in found)


@pytest.mark.parametrize("make", [
    lambda: ExactGramOperator(jnp.arange(6.0).reshape(3, 2),
                              KernelConfig("rbf")),
    lambda: LowRankGramOperator(jnp.arange(12.0).reshape(4, 3)),
    lambda: LowRankGramOperator(
        jnp.arange(12.0).reshape(4, 3),
        fmap=NystromMap(jnp.ones((3, 2)), jnp.eye(3))),
    lambda: NystromMap(jnp.ones((3, 2)), jnp.eye(3),
                       KernelConfig("linear")),
], ids=["exact", "lowrank", "lowrank+fmap", "nystrom"])
def test_registered_pytree_roundtrip(make):
    obj = make()
    leaves, treedef = jax.tree_util.tree_flatten(obj)
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert type(back) is type(obj)
    for a, b in zip(leaves, jax.tree_util.tree_leaves(back)):
        assert jnp.array_equal(a, b)


# ------------------------------------------------ collective census -----

class _Prim:
    def __init__(self, name):
        self.name = name


class _Eqn:
    def __init__(self, name, params=None):
        self.primitive = _Prim(name)
        self.params = params or {}


class _Jaxpr:
    def __init__(self, eqns):
        self.eqns = eqns


@pytest.mark.parametrize("prim", sorted(COLLECTIVE_PRIMS))
def test_every_collective_prim_counted(prim):
    inner = _Jaxpr([_Eqn(prim, {"axes": ("model",)})])
    assert collective_census(inner) == ((prim, ("model",), 1),)
    # under a length-3 scan the site executes 3 times
    outer = _Jaxpr([_Eqn("scan", {"length": 3, "jaxpr": inner})])
    assert collective_census(outer) == ((prim, ("model",), 3),)
    assert count_collective_executions(outer) == 3


def test_census_counts_real_psum_under_scan():
    mesh = make_mesh_auto((1,), ("model",))

    @partial(shard_map, mesh=mesh, in_specs=(P("model"),),
             out_specs=P("model"), check_vma=False)
    def f(x):
        def body(c, _):
            return c + jax.lax.psum(jnp.sum(x), "model"), None
        c, _ = jax.lax.scan(body, 0.0, None, length=7)
        return x + c

    jaxpr = jax.make_jaxpr(f)(jnp.ones((4,), jnp.float32))
    census = collective_census(jaxpr)
    assert count_collective_executions(jaxpr) == 7
    assert all(u.axes == ("model",) for u in census)


# ----------------------------------------------- comm-schedule smoke -----

def test_comm_audit_full_matrix_clean():
    """The acceptance invariant: for all four solvers x {1d, 2d} x
    {linear, rbf}, traced collective executions match the modeled
    schedule and s-step communicates exactly 1/s as often."""
    assert comm_check.audit() == []


@pytest.mark.parametrize("problem,layout", sorted(comm_check.SOLVERS))
def test_sstep_executions_are_classical_over_s(problem, layout):
    for kernel in comm_check.KERNEL_NAMES:
        setup = setup_collectives(layout, kernel)
        cl = comm_check.expected_executions(
            comm_check.CommCase(problem, layout, "classical", kernel))
        ss = comm_check.expected_executions(
            comm_check.CommCase(problem, layout, "sstep", kernel))
        assert (cl - setup) == comm_check.S * (ss - setup)


def test_extra_psum_fails_the_count():
    """Positive fixture: a schedule with one extra collective per round
    must trip CHK-COMM when audited against the model."""
    case = comm_check.CommCase("krr", "1d", "sstep", "linear")
    census = comm_check.trace_case(case)
    doubled = tuple(u._replace(executions=2 * u.executions)
                    for u in census)
    found = comm_check.audit_case(case, doubled)
    assert [f.check for f in found] == ["CHK-COMM"]
    assert comm_check.audit_case(case, census) == []


def test_unknown_axis_name_caught():
    case = comm_check.CommCase("ksvm", "1d", "classical", "linear")
    census = comm_check.trace_case(case)
    renamed = tuple(u._replace(axes=("ring",)) for u in census)
    found = comm_check.audit_case(case, renamed)
    assert "CHK-AXIS" in {f.check for f in found}


# ------------------------------------------------- CHK-CARRY (guard) ----

def test_guard_check_accepts_real_carries():
    """The real guarded families + the real health predicate: every
    carry leaf is covered, no findings."""
    from repro.analysis import guard_check
    assert guard_check.run() == []


def test_guard_check_flags_blind_predicate(monkeypatch):
    """A predicate that reads only the first carry leaf leaves the rest
    unguarded — CHK-CARRY must fire for each missed floating leaf, per
    family, anchored at the factory def line."""
    from repro.analysis import guard_check

    def half_blind(state):
        leaves = jax.tree_util.tree_leaves(state)
        return jnp.all(jnp.isfinite(leaves[0]))

    monkeypatch.setattr(guard_check, "finite_health", half_blind)
    found = guard_check.run()
    assert found and all(f.check == "CHK-CARRY" for f in found)
    assert all(f.severity == ERROR for f in found)
    assert len(found) == 4                    # one missed leaf x family
    assert all(f.line > 0 and f.path.endswith(".py") for f in found)


def test_guard_check_flags_rejecting_predicate(monkeypatch):
    """A predicate that rejects healthy carries freezes every guarded
    solve at round 0 — also a finding."""
    from repro.analysis import guard_check
    monkeypatch.setattr(guard_check, "finite_health",
                        lambda state: jnp.asarray(False))
    found = guard_check.run()
    assert len(found) == 4
    assert all("rejects a finite" in f.message for f in found)


# --------------------------------------------------------- tree gate -----

def test_tree_is_clean_under_full_analysis():
    findings = run_all()
    active = [f for f in findings if not f.suppressed]
    assert active == [], [f.format() for f in active]
    assert all(f.justification for f in findings if f.suppressed)
