"""Tests of ``bench/scopes.py`` and the four readers built on it: the
xplane metadata decoder on a recorded chip trace, the innermost-op
sweep, and each reader on synthetic intervals, including the ``None``
each returns when the accounting does not add up."""
from __future__ import annotations

import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import devtrace, scopes, spec  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"

# one fit on one device: schedule [0, 1], train_op [1, 2], solve [2, 10];
# the solve's program jit_f(7) runs [2, 8.5] as one while loop, and a
# second program jit_g(8) whose op shares the name fusion.1 runs
# [8.5, 9]; the device is idle [0, .2], [.8, 2] and [9, 10]
OPS = [("fusion.9", 0.2, 0.8),
       ("while.1", 2.0, 8.5),
       ("fusion.1", 2.0, 5.0),
       ("fusion.2", 5.0, 6.0),
       ("copy.3", 6.0, 7.0),
       ("fusion.4", 7.0, 7.5),
       ("pad.5", 7.5, 8.5),
       ("fusion.1", 8.5, 9.0)]
MODULES = [("jit_h(9)", 0.2, 0.8), ("jit_f(7)", 2.0, 8.5),
           ("jit_g(8)", 8.5, 9.0)]
HOST = [("bench.window", 0.0, 10.0), ("bench.fit", 0.0, 10.0),
        ("repro.fit", 0.0, 10.0), ("repro.schedule", 0.0, 1.0),
        ("repro.train_op", 1.0, 2.0), ("repro.solve", 2.0, 10.0)]
META = {DEV: {
    (7, "while.1"): "jit(f)/while:",
    (7, "fusion.1"): "jit(f)/while/body/kmv/dot_general:",
    (7, "fusion.2"): "jit(f)/while/body/recurrence/while/body/mul:",
    (7, "fusion.4"): "jit(f)/while/body/scatter/scatter-add:",
    (7, "pad.5"): "jit(f)/while/body/kmv/pad:",
    (8, "fusion.1"): "jit(g)/cross_block/exp:"}}
ROUNDS = 4


def _ctx(monkeypatch, host=HOST, fits=None):
    monkeypatch.setattr(scopes, "decode", lambda path: META)
    monkeypatch.setattr(devtrace, "find_xplane", lambda d: "trace.pb")
    trace = devtrace.Trace(ops={DEV: list(OPS)}, host=list(host),
                           modules={DEV: list(MODULES)})
    fits = fits if fits is not None else [types.SimpleNamespace(
        spans=[("schedule", 0, 1), ("solve", 2, 10)], rounds=ROUNDS)]
    return types.SimpleNamespace(
        window=(0.0, 10.0), trace=trace, notes=[],
        driver=types.SimpleNamespace(fits=fits, opts={"s": 16, "b": 1}),
        cell=types.SimpleNamespace(config={"problem": "ksvm", "m": 1000,
                                           "n": 54}),
        peaks={"flops_bf16": 1e12, "hbm_bytes_per_s": 1e9})


def _read(metric, ctx):
    return spec.layer_reader(metric).read(ctx)


def test_decoder_reads_tf_op_paths_of_a_recorded_trace():
    meta = scopes.decode(str(DATA / "small_trace.xplane.pb"))
    assert set(meta) == {DEV}
    tf = {name: path for (_, name), path in meta[DEV].items()}
    assert tf["fusion.5"] == "jit(kmv)/dot_general:"
    # ops the compiler made (copies) carry no tf_op
    assert not any(name.startswith("copy") for name in tf)
    pids = {pid for pid, _ in meta[DEV]}
    tr = devtrace.load(str(DATA / "small_trace.xplane.pb"))
    assert {int(scopes.PROGRAM_ID.search(n).group(1))
            for n, _, _ in tr.modules[DEV]} == pids


def test_exclusive_gives_each_instant_to_the_innermost_op():
    got = scopes.exclusive([("w", 0, 10), ("a", 1, 2), ("b", 1.5, 3),
                            ("c", 5, 6), ("d", 9, 12)])
    assert got == pytest.approx({"w": 6.0, "a": 0.5, "b": 1.5, "c": 1.0,
                                 "d": 3.0})
    assert sum(got.values()) == pytest.approx(12.0)     # the union


def test_phases_tell_programs_apart_and_add_up_to_busy(monkeypatch):
    ctx = _ctx(monkeypatch)
    ph = scopes.solve_phases(ctx)
    assert ph is not None and ph.n_spans == 1
    assert ph.busy == pytest.approx(7.0)
    assert ph.owners() == pytest.approx({
        "kmv": 4.0, "recurrence": 1.0, "scatter": 0.5,
        "cross_block": 0.5, "unscoped": 1.0})
    assert ph.owners_of("pad") == [("kmv", pytest.approx(1.0))]
    assert ph.families("unscoped") == [("copy", pytest.approx(1.0))]
    assert ph.accounted() is None
    assert scopes.solve_phases(ctx) is ph                  # decoded once


def test_round_kmv_reader_and_its_phase_notes(monkeypatch):
    ctx = _ctx(monkeypatch)
    assert _read("round_kmv_us.fit", ctx) == pytest.approx(1e6 * 4.0 / 4)
    notes = "\n".join(ctx.notes)
    assert "phases per round (4 rounds" in notes
    assert "unscoped, largest op families: copy 250000.0 us" in notes
    assert ("owner of pad: kmv 250000.0 us; tf_op "
            "jit(f)/while/body/kmv/pad:") in notes
    assert "owner of copy: unscoped 250000.0 us; tf_op none" in notes


def test_round_recurrence_reader(monkeypatch):
    ctx = _ctx(monkeypatch)
    assert _read("round_recurrence_us.fit", ctx) == pytest.approx(
        1e6 * 1.5 / 4)


def test_kmv_fit_roofline_reader(monkeypatch):
    ctx = _ctx(monkeypatch)
    t_min, _ = spec.layer_reader("kmv_roofline.fit").bound(
        1000, 54, 16, ctx.peaks)
    # kmv owns 4 s over 4 rounds: 1 s a round
    assert _read("kmv_fit_roofline.fit", ctx) == pytest.approx(
        100.0 * t_min / 1.0)


def test_fit_idle_reader_puts_each_gap_down_to_a_span(monkeypatch):
    ctx = _ctx(monkeypatch)
    assert _read("fit_idle_ms.fit", ctx) == pytest.approx(1e3 * 2.4)
    note, = [n for n in ctx.notes if n.startswith("idle per fit")]
    # a gap goes to the span that covers its midpoint: [.8, 2] is the
    # train_op's
    assert "train_op 1200.000 ms" in note and "solve 1000.000 ms" in note
    assert "schedule 200.000 ms" in note
    assert "outside the fits: 0.000 ms" in note


def test_fit_idle_reader_without_fit_spans(monkeypatch):
    host = [e for e in HOST if not e[0].startswith("repro.")]
    assert _read("fit_idle_ms.fit", _ctx(monkeypatch, host=host)) is None


@pytest.mark.parametrize("metric", ["round_kmv_us.fit",
                                    "round_recurrence_us.fit",
                                    "kmv_fit_roofline.fit"])
def test_readers_refuse_when_the_accounting_fails(monkeypatch, metric):
    ctx = _ctx(monkeypatch)
    monkeypatch.setattr(scopes, "phases", lambda *a: scopes.Phases(
        span="repro.solve", n_spans=1, by={("kmv", "fusion"): 6.0},
        busy=7.0))
    assert _read(metric, ctx) is None
    assert any("!= busy" in n for n in ctx.notes)


@pytest.mark.parametrize("metric", ["round_kmv_us.fit",
                                    "round_recurrence_us.fit",
                                    "kmv_fit_roofline.fit"])
def test_readers_are_silent_on_a_program_without_scopes(monkeypatch,
                                                        metric):
    ctx = _ctx(monkeypatch)
    monkeypatch.setattr(scopes, "SCOPES", ())
    assert _read(metric, ctx) is None
    ctx = _ctx(monkeypatch, host=[e for e in HOST
                                  if not e[0].startswith("repro.")])
    assert _read(metric, ctx) is None


def test_readers_refuse_a_profile_that_dropped_events(monkeypatch):
    # the same fit twice, the second from 10 s on, with one of its ops
    # lost: fewer device ops per round than the first
    late = [(n, a + 10, b + 10) for n, a, b in OPS]
    ctx = _ctx(monkeypatch, fits=[types.SimpleNamespace(
        spans=[("solve", 2, 10)], rounds=ROUNDS)] * 2)
    ctx.window = (0.0, 20.0)
    ctx.trace = devtrace.Trace(
        ops={DEV: OPS + late[:-2] + late[-1:]},
        host=HOST + [(n, a + 10, b + 10) for n, a, b in HOST[2:]],
        modules={DEV: MODULES + [(n, a + 10, b + 10)
                                 for n, a, b in MODULES]})
    assert _read("round_kmv_us.fit", ctx) is None
    assert any("dropped device events: 7 ops in 4 rounds, 6 ops" in n
               for n in ctx.notes)
    # the complete profile of the same two fits reads as one fit does
    ctx = _ctx(monkeypatch, fits=ctx.driver.fits)
    ctx.window = (0.0, 20.0)
    ctx.trace = devtrace.Trace(
        ops={DEV: OPS + late},
        host=HOST + [(n, a + 10, b + 10) for n, a, b in HOST[2:]],
        modules={DEV: MODULES + [(n, a + 10, b + 10)
                                 for n, a, b in MODULES]})
    assert _read("round_kmv_us.fit", ctx) == pytest.approx(1e6 * 8 / 8)


def test_rounds_need_one_solve_span_per_traced_fit(monkeypatch):
    fit = types.SimpleNamespace(spans=[("solve", 2, 10)], rounds=ROUNDS)
    ctx = _ctx(monkeypatch, fits=[fit, fit])
    assert _read("round_kmv_us.fit", ctx) is None
    assert any("1 repro.solve annotations in the trace, 2 traced fits" in n
               for n in ctx.notes)


def test_readers_on_a_recorded_scoped_fit(monkeypatch):
    """One scoped K-SVM fit on a TPU v5e (m = 65,536, n = 54, s = 16,
    4 rounds, telemetry on; ``bench/tracing.py --record-trace``)."""
    path = str(DATA / "scoped_fit.xplane.pb")
    monkeypatch.setattr(devtrace, "find_xplane", lambda d: path)
    trace = devtrace.load(path)
    fit = types.SimpleNamespace(spans=[("solve", 0, 1)], rounds=4)
    ctx = types.SimpleNamespace(
        window=trace.annotation("bench.window"), trace=trace, notes=[],
        driver=types.SimpleNamespace(fits=[fit], opts={"s": 16, "b": 1}),
        cell=types.SimpleNamespace(config={"problem": "ksvm",
                                           "m": 65536, "n": 54}),
        peaks=spec.peaks("TPU v5 lite"))
    ph = scopes.solve_phases(ctx)
    assert ph is not None, ctx.notes
    owners = ph.owners()
    assert {"kmv", "cross_block", "recurrence", "scatter"} <= set(owners)
    kmv = _read("round_kmv_us.fit", ctx)
    rec = _read("round_recurrence_us.fit", ctx)
    assert 0 < kmv and 0 < rec
    assert kmv + rec <= 1e6 * ph.busy / 4
    assert 0 < _read("kmv_fit_roofline.fit", ctx) <= 100
    assert _read("fit_idle_ms.fit", ctx) > 0
    note, = [n for n in ctx.notes if n.startswith("idle per fit")]
    assert "train_op" in note and "schedule" in note
