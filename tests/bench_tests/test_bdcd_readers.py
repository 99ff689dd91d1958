"""The per-layer readers of ``krr-msd.fit-bdcd`` on a synthetic K-RR trace:
the cell reports the accepted fit metrics and ``bdcd_block_solve_us.fit``;
each reads the K-RR trace at s*b = 128; on the same trace from a program
that names no ``block_solve`` scope the block-solve reader gives nothing
(never 0) and ``recurrence`` holds the solves again."""
from __future__ import annotations

import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import devtrace, scopes, spec  # noqa: E402

CELL = "krr-msd.fit-bdcd"
ACCEPTED = ["facade_ms.fit", "round_us.fit", "kmv_roofline.fit",
            "device_idle.fit", "round_kmv_us.fit", "round_recurrence_us.fit",
            "kmv_fit_roofline.fit", "fit_idle_ms.fit"]
DEV = "/device:TPU:0"
# one fit [0, 10]: schedule [0, 1], solve [2, 10]; its program jit_f(7)
# runs [2, 9] as one while loop whose body runs the KMV, the corrections,
# the solves, the cross block and the scatter; after the window the
# probe's 51 calls of jit_kmv_probe(9) take 0.51 s
OPS = [("while.1", 2.0, 9.0),
       ("fusion.1", 2.0, 5.0),
       ("fusion.2", 5.0, 6.0),
       ("custom-call.3", 6.0, 7.5),
       ("fusion.4", 7.5, 8.0),
       ("fusion.5", 8.0, 8.5)]
MODULES = [("jit_f(7)", 2.0, 9.0), ("jit_kmv_probe(9)", 10.0, 10.51)]
HOST = [("bench.window", 0.0, 10.0), ("bench.fit", 0.0, 10.0),
        ("repro.fit", 0.0, 10.0), ("repro.schedule", 0.0, 1.0),
        ("repro.solve", 2.0, 10.0)]
BODY = "jit(f)/while/body/"
META = {DEV: {
    (7, "while.1"): "jit(f)/while:",
    (7, "fusion.1"): BODY + "kmv/dot_general:",
    (7, "fusion.2"): BODY + "recurrence/while/body/mul:",
    (7, "custom-call.3"): BODY + "recurrence/while/body/block_solve/"
                                 "jit(solve)/lu:",
    (7, "fusion.4"): BODY + "cross_block/exp:",
    (7, "fusion.5"): BODY + "scatter/scatter-add:"}}
ROUNDS = 4
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
# kmv_roofline.fit's least KMV time at the cell's (463,715, 90, 128)
T_MIN = 4 * (463715 * 90 + 128 * 90 + 463715) / PEAKS["hbm_bytes_per_s"]


def _ctx(monkeypatch, fits=None):
    monkeypatch.setattr(scopes, "decode", lambda path: META)
    monkeypatch.setattr(devtrace, "find_xplane", lambda d: "trace.pb")
    trace = devtrace.Trace(ops={DEV: list(OPS)}, host=list(HOST),
                           modules={DEV: list(MODULES)})
    fits = fits if fits is not None else [types.SimpleNamespace(
        spans=[("schedule", 0, 1), ("solve", 2, 10)], rounds=ROUNDS,
        t0=0.0, t1=10.0)]
    return types.SimpleNamespace(
        window=(0.0, 10.0), trace=trace, notes=[],
        driver=types.SimpleNamespace(fits=fits, opts={"s": 16, "b": 8}),
        cell=types.SimpleNamespace(config={"problem": "krr", "m": 463715,
                                           "n": 90}),
        probes={"kmv_roofline.fit": {"r": 128}}, peaks=PEAKS)


def _parent(monkeypatch):
    """The program before ``block_solve``: the solve is ``recurrence``'s."""
    real = scopes.owner
    monkeypatch.setattr(scopes, "SCOPES", tuple(
        s for s in scopes.SCOPES if s != "block_solve"))
    monkeypatch.setattr(scopes, "owner", lambda p: real(
        p.replace("block_solve/", "")))


def _read(metric, ctx):
    return spec.layer_reader(metric).read(ctx)


def test_cell_reports_the_accepted_metrics_and_block_solve():
    names = [m["name"] for m in spec.load_cell(CELL).per_layer]
    assert names == ACCEPTED + ["bdcd_block_solve_us.fit"]
    assert "bdcd_block_solve_us.fit" not in [
        m["name"] for m in spec.load_cell("ksvm-covtype.fit").per_layer]


def test_block_solve_and_local_readers(monkeypatch):
    ctx = _ctx(monkeypatch)
    assert _read("bdcd_block_solve_us.fit", ctx) == pytest.approx(
        1e6 * 1.5 / ROUNDS)
    # the corrections and the scatter, without the solves
    assert _read("round_recurrence_us.fit", ctx) == pytest.approx(
        1e6 * (1.0 + 0.5) / ROUNDS)
    _read("round_kmv_us.fit", ctx)                   # notes the phases
    notes = "\n".join(ctx.notes)
    assert "block_solve 375000.0 us" in notes
    assert "recurrence 250000.0 us" in notes


def test_round_and_roofline_readers(monkeypatch):
    ctx = _ctx(monkeypatch)
    assert _read("round_us.fit", ctx) == pytest.approx(1e6 * 8.0 / ROUNDS)
    t_min, which = spec.layer_reader("kmv_roofline.fit").bound(
        463715, 90, 128, PEAKS)
    assert which == "bytes" and t_min == pytest.approx(T_MIN)
    assert t_min == pytest.approx(206.2e-6, rel=1e-3)
    assert _read("kmv_fit_roofline.fit", ctx) == pytest.approx(
        100 * t_min / (3.0 / ROUNDS))


@pytest.mark.parametrize("metric,want", [
    ("facade_ms.fit", 1e3 * (10.0 - 8.0)),
    ("device_idle.fit", 100 * (1 - 7.0 / 10.0)),
    ("fit_idle_ms.fit", 1e3 * 3.0),
    ("round_kmv_us.fit", 1e6 * 3.0 / ROUNDS),
    ("kmv_roofline.fit", 100 * 50 * T_MIN / 0.5),
])
def test_accepted_readers_read_a_krr_trace(monkeypatch, metric, want):
    assert _read(metric, _ctx(monkeypatch)) == pytest.approx(want)


def test_readers_give_nothing_without_the_block_solve_scope(monkeypatch):
    _parent(monkeypatch)
    ctx = _ctx(monkeypatch)
    assert _read("bdcd_block_solve_us.fit", ctx) is None
    # the accepted readers still read the parent's trace, whose
    # recurrence holds the solves
    assert _read("round_recurrence_us.fit", ctx) == pytest.approx(
        1e6 * (1.0 + 1.5 + 0.5) / ROUNDS)
    assert _read("round_us.fit", ctx) is not None
    assert _read("kmv_fit_roofline.fit", ctx) is not None


@pytest.mark.parametrize("metric", ["facade_ms.fit", "round_us.fit",
                                    "round_kmv_us.fit",
                                    "round_recurrence_us.fit",
                                    "kmv_fit_roofline.fit",
                                    "bdcd_block_solve_us.fit"])
def test_readers_give_nothing_without_solve_spans(monkeypatch, metric):
    ctx = _ctx(monkeypatch, fits=[types.SimpleNamespace(
        spans=[("schedule", 0, 1)], rounds=ROUNDS, t0=0.0, t1=10.0)])
    ctx.trace.host[:] = [e for e in HOST if e[0] != "repro.solve"]
    assert _read(metric, ctx) is None
