"""repro.obs tests: metrics registry semantics, span/mark recording,
the round's named scopes in every driver's compiled program, one
program with telemetry on or off, spans as profiler annotations,
end-to-end instrumented fits, Chrome-trace export, serving metrics,
and the CLI (DESIGN.md §15)."""
import glob
import json
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import KernelRidge, SolverOptions
from repro.obs import MetricsRegistry, SCOPES, Telemetry, default_registry
from repro.obs import scopes
from repro.obs.export import (load_trace, save_trace, to_chrome_trace,
                              validate_chrome_trace)


SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def _problem(m=48, n=4, seed=0):
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    y = jnp.asarray(np.asarray(A) @ rng.standard_normal(n), jnp.float32)
    return A, y


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter_inc_and_labels(self):
        reg = MetricsRegistry()
        c = reg.counter("requests_total", "total requests")
        c.inc()
        c.inc(2.0, route="a")
        c.inc(route="a")
        assert c.value() == 1.0
        assert c.value(route="a") == 3.0

    def test_counter_rejects_decrease(self):
        c = MetricsRegistry().counter("x_total")
        with pytest.raises(ValueError, match="cannot decrease"):
            c.inc(-1.0)

    def test_gauge_set_and_negative_inc(self):
        g = MetricsRegistry().gauge("depth")
        g.set(5.0)
        g.inc(-2.0)
        assert g.value() == 3.0

    def test_histogram_quantile_and_overflow(self):
        h = MetricsRegistry().histogram("lat", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 0.7, 5.0):   # 5.0 lands in +Inf overflow
            h.observe(v)
        q50 = h.quantile(0.5)
        assert 0.1 <= q50 <= 1.0
        with pytest.raises(ValueError, match="quantile"):
            h.quantile(1.5)

    def test_histogram_empty_quantile_nan(self):
        h = MetricsRegistry().histogram("lat2", buckets=(1.0,))
        assert np.isnan(h.quantile(0.5))

    def test_kind_clash_raises(self):
        reg = MetricsRegistry()
        reg.counter("thing")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("thing")
        # same kind + name returns the same instrument
        assert reg.counter("thing") is reg.counter("thing")

    def test_bound_labels_fast_path(self):
        reg = MetricsRegistry()
        c = reg.counter("t_total")
        done = c.labels(status="done")
        done.inc()
        done.inc(2.0)
        assert c.value(status="done") == 3.0
        with pytest.raises(ValueError, match="cannot decrease"):
            done.inc(-1.0)
        with pytest.raises(TypeError, match="no set"):
            done.set(5.0)
        g = reg.gauge("d")
        bound = g.labels()
        bound.set(4.0)
        bound.inc(-1.0)
        assert g.value() == 3.0

    def test_prometheus_text_shape(self):
        reg = MetricsRegistry()
        reg.counter("c_total", "a counter").inc(status="ok")
        reg.gauge("g", "a gauge").set(2.5)
        reg.histogram("h_seconds", "a histogram",
                      buckets=(0.1, 1.0)).observe(0.5)
        text = reg.to_prometheus_text()
        assert "# HELP c_total a counter" in text
        assert "# TYPE c_total counter" in text
        assert 'c_total{status="ok"} 1' in text
        assert "# TYPE h_seconds histogram" in text
        assert 'h_seconds_bucket{le="+Inf"} 1' in text
        assert "h_seconds_sum" in text and "h_seconds_count 1" in text
        # json round-trips
        payload = json.loads(reg.to_json())
        assert set(payload) == {"c_total", "g", "h_seconds"}
        assert payload["c_total"]["kind"] == "counter"
        assert payload["h_seconds"]["values"]["count"] == 1

    def test_default_registry_is_process_singleton(self):
        assert default_registry() is default_registry()


# ---------------------------------------------------------------------------
# Telemetry spans and marks
# ---------------------------------------------------------------------------

class TestTelemetry:
    def test_span_and_mark_recording(self):
        tel = Telemetry()
        with tel.span("build", "setup", m=8):
            tel.mark("seam", phase="solve", value=3.0)
        assert len(tel.spans) == 1 and len(tel.marks) == 1
        sp = tel.spans[0]
        assert sp.name == "build" and sp.phase == "setup"
        assert sp.duration >= 0 and sp.args == {"m": 8}
        assert tel.marks[0].value == 3.0
        lo, hi = tel.window()
        assert lo <= hi
        tel.clear()
        assert tel.spans == [] and tel.marks == []
        assert tel.window() is None

    def test_disabled_handle_records_nothing(self):
        tel = Telemetry(enabled=False)
        with tel.span("x"):
            tel.mark("y")
        assert tel.spans == [] and tel.marks == []
        assert tel.window() is None


# ---------------------------------------------------------------------------
# named scopes: the round's phases in the compiled program
# ---------------------------------------------------------------------------

def _op_owners(text: str) -> set:
    """The scopes that own some op of a compiled program's text."""
    return {scopes.owner(p) for p in re.findall(r'op_name="([^"]*)"', text)
            } - {None}


def _small():
    A, y = _problem(m=32)
    return A, jnp.sign(y) + (y == 0), jnp.zeros(32)


def _sstep_dcd():
    from repro.core import SVMConfig
    from repro.core.sstep_dcd import sstep_dcd_ksvm
    A, y, a0 = _small()
    return sstep_dcd_ksvm.lower(A, y, a0, jnp.zeros(16, jnp.int32),
                                SVMConfig(), 4)


def _sstep_bdcd():
    from repro.core import KRRConfig
    from repro.core.sstep_bdcd import sstep_bdcd_krr
    A, y, a0 = _small()
    return sstep_bdcd_krr.lower(A, y, a0, jnp.zeros((8, 2), jnp.int32),
                                KRRConfig(), 4)


def _dcd():
    from repro.core import SVMConfig
    from repro.core.dcd import dcd_ksvm
    A, y, a0 = _small()
    return dcd_ksvm.lower(A, y, a0, jnp.zeros(8, jnp.int32), SVMConfig())


def _bdcd():
    from repro.core import KRRConfig
    from repro.core.bdcd import bdcd_krr
    A, y, a0 = _small()
    return bdcd_krr.lower(A, y, a0, jnp.zeros((8, 2), jnp.int32),
                          KRRConfig())


def _guarded_args():
    from repro.core import KRRConfig
    from repro.core.kernels import ExactGramOperator, KernelConfig
    A, y, a0 = _small()
    cfg = KRRConfig(lam=1.0, kernel=KernelConfig("rbf"))
    return (A, y, a0, jnp.zeros(32), jnp.zeros((8, 2), jnp.int32),
            jnp.asarray(1e-6), -1, float("nan")), dict(
        problem="krr", cfg=cfg, s=4, check_every=2, correct_every=1,
        lowrank=False, want_metric=True,
        op=ExactGramOperator(A, cfg.kernel))


def _guarded():
    from repro.api import _guarded_serial_chunk
    args, kw = _guarded_args()
    return _guarded_serial_chunk.lower(*args, **kw)


def _tol_args():
    from repro.core import KRRConfig
    from repro.core.kernels import ExactGramOperator, KernelConfig
    A, y, a0 = _small()
    cfg = KRRConfig(lam=1.0, kernel=KernelConfig("rbf"))
    return (A, y, a0, jnp.zeros((8, 2), jnp.int32), jnp.asarray(1e-6)), \
        dict(cfg=cfg, s=4, check_every=2, slab_free=True,
             op=ExactGramOperator(A, cfg.kernel))


def _tol():
    from repro.api import _krr_serial_tol
    args, kw = _tol_args()
    return _krr_serial_tol.lower(*args, **kw)


def _fleet():
    from repro.core import KRRConfig
    from repro.core.kernels import ExactGramOperator
    from repro.tune.fleet import _fleet_serial
    A, y, _ = _small()
    cfg = KRRConfig()
    return _fleet_serial.lower(
        A, y, jnp.zeros((3, 32)), jnp.asarray([0.5, 1.0, 2.0]),
        jnp.zeros((8, 2), jnp.int32), jnp.asarray(1e-6),
        ExactGramOperator(A, cfg.kernel), problem="krr", cfg=cfg, s=4,
        check_every=2, want_metric=True)


ROUND = {"kmv", "cross_block", "recurrence", "scatter"}
SERIAL_DRIVERS = {
    "sstep_dcd": (_sstep_dcd, ROUND),
    "sstep_bdcd": (_sstep_bdcd, ROUND),
    "dcd": (_dcd, ROUND),
    "bdcd": (_bdcd, ROUND),
    # the guarded round reads U^T alpha from its carried residual and
    # advances it by apply_at; the drift correction recomputes it
    "guarded": (_guarded, {"kmv_apply", "cross_block", "recurrence",
                           "scatter", "metric_check", "drift_correction"}),
    "tol": (_tol, ROUND | {"metric_check"}),
    "fleet": (_fleet, ROUND | {"metric_check"}),
}
LAYOUT_DRIVERS = {"1d": ROUND | {"psum"}, "2d": ROUND | {"psum"}}

_DIST_SCRIPT = r"""
import json, re
import jax, jax.numpy as jnp
from repro.api import _dist_call
from repro.core import KRRConfig, SVMConfig
from repro.compat import make_mesh_auto
out = {}
A = jnp.ones((32, 8)) / 8
y = jnp.where(jnp.arange(32) % 2 == 0, 1.0, -1.0)
for layout, shape in (("1d", (1, 4)), ("2d", (2, 2))):
    mesh = make_mesh_auto(shape, ("data", "model"))
    for problem, cfg, sched in (
            ("ksvm", SVMConfig(), jnp.zeros(16, jnp.int32)),
            ("krr", KRRConfig(), jnp.zeros((8, 2), jnp.int32))):
        f = jax.jit(lambda A, y, a0, sc: _dist_call(
            problem, layout, mesh, A, y, a0, sc, cfg, 4, True))
        text = f.lower(A, y, jnp.zeros(32), sched).compile().as_text()
        out[f"{layout}.{problem}"] = sorted(set(
            re.findall(r'op_name="([^"]*)"', text)))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def layout_op_names():
    """op_name paths of the 1d and 2d solvers, compiled on four virtual
    CPU devices in a child process (this process keeps one device)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    out = subprocess.run([sys.executable, "-c", _DIST_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


class TestScopes:
    @pytest.mark.parametrize("driver", sorted(SERIAL_DRIVERS))
    def test_compiled_text_carries_scopes(self, driver):
        lower, want = SERIAL_DRIVERS[driver]
        got = _op_owners(lower().compile().as_text())
        assert want <= got, f"{driver}: missing {want - got}"
        assert got <= set(SCOPES)

    @pytest.mark.parametrize("layout", sorted(LAYOUT_DRIVERS))
    def test_layouts_carry_scopes(self, layout, layout_op_names):
        want = LAYOUT_DRIVERS[layout]
        for problem in ("ksvm", "krr"):
            got = {scopes.owner(p)
                   for p in layout_op_names[f"{layout}.{problem}"]} - {None}
            assert want <= got, f"{layout}.{problem}: missing {want - got}"

    @pytest.mark.parametrize("path,want", [
        ("jit(f)/while/body/kmv/dot_general", "kmv"),
        ("jit(f)/while/body/scatter/kmv_apply/dot_general:", "kmv_apply"),
        ("jit(f)/while/body/recurrence/while/body/scatter", "recurrence"),
        ("jit(kmv)/dot_general:", None),
        ("jit(f)/vmap()/while/body/psum/psum", "psum"),
        ("jit(f)/while/body/vmap(cross_block)/jit(gram_slab)/exp",
         "cross_block"),
        ("jit(f)/metric_check/vmap(jit(norm))/sqrt", "metric_check"),
        ("x", None)])
    def test_owner_is_innermost_scope_before_the_op(self, path, want):
        assert scopes.owner(path) == want

    def test_unknown_scope_refused(self):
        with pytest.raises(ValueError, match="unknown scope"):
            scopes.scoped("kmv_pad")


def _lowered_texts(monkeypatch, name):
    """Run the next fits with ``repro.api.<name>`` recording the text of
    every program it lowers."""
    import repro.api as api
    fn = getattr(api, name)
    texts = []

    def recording(*args, **kw):
        texts.append(fn.lower(*args, **kw).as_text())
        return fn(*args, **kw)

    monkeypatch.setattr(api, name, recording)
    return texts


class TestOneProgram:
    """Telemetry on and off run one compiled program: the scopes are
    metadata, and nothing telemetry-dependent reaches the trace."""

    @pytest.mark.parametrize("path,entry,opts", [
        ("tol", "_krr_serial_tol", dict(tol=1e-10, check_every=4)),
        ("guarded", "_guarded_serial_chunk",
         dict(tol=1e-10, check_every=4, guard=True, recompute_every=4))])
    def test_telemetry_on_off_same_program(self, monkeypatch, path, entry,
                                           opts):
        texts = _lowered_texts(monkeypatch, entry)
        A, y = _problem()
        for tel in (None, Telemetry(), Telemetry(enabled=False)):
            KernelRidge(lam=0.5, kernel="rbf", options=SolverOptions(
                method="sstep", s=4, b=4, max_iters=64, telemetry=tel,
                **opts)).fit(A, y)
        assert len(texts) == 3
        assert texts[0] == texts[1] == texts[2]
        assert "callback" not in texts[0]


class TestSpansOnTheProfilersClock:
    def test_fit_spans_are_nested_profiler_annotations(self, tmp_path):
        from jax.profiler import ProfileData
        A, y = _problem()
        opts = dict(method="sstep", s=4, b=4, max_iters=32)
        KernelRidge(lam=0.5, kernel="rbf",
                    options=SolverOptions(**opts)).fit(A, y)   # compile
        with jax.profiler.trace(str(tmp_path)):
            KernelRidge(lam=0.5, kernel="rbf", options=SolverOptions(
                telemetry=Telemetry(enabled=False), **opts)).fit(A, y)
            KernelRidge(lam=0.5, kernel="rbf", options=SolverOptions(
                telemetry=Telemetry(), **opts)).fit(A, y)
        path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                              "*.xplane.pb"))
        ev = {}
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        ev.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
        # the disabled handle opened none: one of each
        assert {k: len(v) for k, v in ev.items()
                if k in ("repro.fit", "repro.schedule", "repro.solve")} \
            == {"repro.fit": 1, "repro.schedule": 1, "repro.solve": 1}
        (f0, f1), = ev["repro.fit"]
        (s0, s1), = ev["repro.schedule"]
        (v0, v1), = ev["repro.solve"]
        assert f0 <= s0 <= s1 <= v0 <= v1 <= f1


# ---------------------------------------------------------------------------
# instrumented fits end to end
# ---------------------------------------------------------------------------

class TestInstrumentedFit:
    def _fit(self, tel, **opt_kw):
        A, y = _problem()
        kw = dict(method="sstep", s=4, b=4, tol=1e-10, check_every=4,
                  max_iters=64, telemetry=tel)
        kw.update(opt_kw)
        kr = KernelRidge(lam=0.5, kernel="rbf",
                         options=SolverOptions(**kw))
        return kr.fit(A, y)

    def test_fit_records_spans_and_result_carries_handle(self):
        tel = Telemetry()
        res = self._fit(tel)
        assert res.telemetry is tel
        phases = {s.phase for s in tel.spans}
        assert {"setup", "solve", "fit"} <= phases
        names = [s.name for s in tel.spans]
        # closed spans land in end order: the fit's parts, then the fit
        assert names == ["representation_build", "schedule", "solve",
                         "fit"]
        fit = tel.spans[-1]
        assert all(fit.t0 <= s.t0 <= s.t1 <= fit.t1 for s in tel.spans)

    def test_svm_fit_times_its_train_op(self):
        from repro.api import KernelSVM
        A, y = _problem()
        tel = Telemetry()
        KernelSVM(C=1.0, kernel="rbf", options=SolverOptions(
            method="sstep", s=4, max_iters=32, telemetry=tel)).fit(
            A, jnp.sign(y) + (y == 0))
        names = [s.name for s in tel.spans]
        assert names == ["representation_build", "schedule", "train_op",
                         "solve", "fit"]

    def test_guarded_fit_counts_corrections(self):
        tel = Telemetry()
        self._fit(tel, guard=True, recompute_every=4)
        c = tel.metrics.counter("repro_guard_corrections_total")
        assert c.value() >= 1
        assert any(s.name == "guarded_segment" for s in tel.spans)

    def test_no_telemetry_fit_unchanged(self):
        res = self._fit(None)
        assert res.telemetry is None


# ---------------------------------------------------------------------------
# trace export
# ---------------------------------------------------------------------------

class TestTraceExport:
    def _recorded(self):
        tel = Telemetry()
        res = self._res = KernelRidge(
            lam=0.5, kernel="rbf",
            options=SolverOptions(method="sstep", s=4, b=4, tol=1e-10,
                                  check_every=4, max_iters=32,
                                  telemetry=tel)).fit(*_problem())
        return res.telemetry

    def test_chrome_trace_schema(self, tmp_path):
        tel = self._recorded()
        trace = to_chrome_trace(tel)
        validate_chrome_trace(trace)          # must not raise
        evs = trace["traceEvents"]
        assert any(e["ph"] == "X" for e in evs)
        assert all({"name", "ph", "ts", "pid", "tid"} <= set(e)
                   for e in evs if e["ph"] != "M")
        path = save_trace(str(tmp_path / "t.json"), tel)
        back = load_trace(path)
        assert len(back["traceEvents"]) == len(evs)

    def test_validate_rejects_bad_traces(self):
        with pytest.raises(ValueError):
            validate_chrome_trace({})
        with pytest.raises(ValueError):
            validate_chrome_trace({"traceEvents": [
                {"name": "x", "ph": "Q", "ts": 0.0, "pid": 1,
                 "tid": 1}]})
        with pytest.raises(ValueError):
            validate_chrome_trace({"traceEvents": [
                {"name": "x", "ph": "X", "ts": -1.0, "dur": 1.0,
                 "pid": 1, "tid": 1}]})
        with pytest.raises(ValueError):   # unbalanced B without E
            validate_chrome_trace({"traceEvents": [
                {"name": "x", "ph": "B", "ts": 0.0, "pid": 1,
                 "tid": 1}]})


# ---------------------------------------------------------------------------
# serving metrics
# ---------------------------------------------------------------------------

class TestServeMetrics:
    def test_engine_instruments(self):
        from repro.serve import ModelRegistry, ServingEngine
        A, y = _problem(m=32)
        kr = KernelRidge(lam=0.5, kernel="rbf",
                         options=SolverOptions(method="sstep", s=4, b=4,
                                               max_iters=32))
        kr.fit(A, y)
        reg = ModelRegistry(predict_batch=8)
        reg.register("krr", kr)
        tel = Telemetry()
        eng = ServingEngine(reg, slots=8, telemetry=tel)
        Q = np.asarray(_problem(m=16)[0])
        for i in range(16):
            eng.submit("krr", Q[i][None, :])
        eng.run_until_idle()
        c = tel.metrics.counter("repro_serve_tickets_total")
        assert c.value(status="submitted") == 16
        assert c.value(status="done") == 16
        occ = tel.metrics.histogram("repro_serve_batch_occupancy")
        assert occ.quantile(0.5) > 0
        lat = tel.metrics.histogram("repro_serve_ticket_latency_seconds")
        assert not np.isnan(lat.quantile(0.5))
        assert any(s.name == "engine_step" for s in tel.spans)
        text = tel.metrics.to_prometheus_text()
        assert "repro_serve_queue_depth" in text

    def test_engine_without_telemetry_unchanged(self):
        from repro.serve import ModelRegistry, ServingEngine
        A, y = _problem(m=32)
        kr = KernelRidge(lam=0.5, kernel="linear",
                         options=SolverOptions(max_iters=16))
        kr.fit(A, y)
        reg = ModelRegistry(predict_batch=8)
        reg.register("krr", kr)
        eng = ServingEngine(reg, slots=8)
        eng.submit("krr", np.asarray(A[:1]))
        assert eng.run_until_idle() >= 1


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

class TestCLI:
    def test_trace_and_scrape(self, tmp_path, capsys):
        from repro.obs.__main__ import main
        out_path = tmp_path / "t.json"
        assert main(["trace", "--m", "48", "--iters", "32",
                     "--out", str(out_path)]) == 0
        validate_chrome_trace(json.loads(out_path.read_text()))
        assert main(["scrape", "--m", "48", "--iters", "32",
                     "--tickets", "8"]) == 0
        out = capsys.readouterr().out
        assert "repro_serve_tickets_total" in out
