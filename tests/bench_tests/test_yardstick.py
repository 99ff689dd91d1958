"""Tests of the benchmark's yardstick (``bench/``): trace reduction, work
counts, the peaks table, the cell files, and the command's refusal to run
without a chip.  Pure Python: nothing here loads a TPU library."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import devtrace, spec  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def _trace():
    # device 0 busy [0, 1] and [2, 3]; device 1 busy [0.5, 2.5] with an
    # overlapping op inside it
    return devtrace.Trace(
        ops={"/device:TPU:0": [("fusion.1", 0.0, 1.0),
                               ("all-reduce.3", 2.0, 3.0)],
             "/device:TPU:1": [("fusion.2", 0.5, 2.5),
                               ("copy.4", 1.0, 1.5)]},
        host=[("bench.window", 0.0, 4.0), ("bench.fit", 0.0, 3.0)])


def test_union_merges_overlaps():
    iv = [("a", 0.0, 1.0), ("b", 0.5, 2.0), ("c", 3.0, 4.0)]
    assert devtrace.merged(iv) == [(0.0, 2.0), (3.0, 4.0)]
    assert devtrace.union_s(iv) == pytest.approx(3.0)


def test_busy_and_idle_share_average_over_devices():
    tr = _trace()
    # device 0: 2 s busy of [0, 4]; device 1: 2 s
    assert devtrace.busy_s(tr, 0.0, 4.0) == pytest.approx(2.0)
    assert devtrace.idle_pct(tr, 0.0, 4.0) == pytest.approx(50.0)
    # clipped to [0.5, 2.5]: device 0 has 0.5 + 0.5, device 1 has 2.0
    assert devtrace.busy_s(tr, 0.5, 2.5) == pytest.approx(1.5)


def test_idle_share_is_none_without_device_events():
    tr = devtrace.Trace(ops={}, host=[("bench.window", 0.0, 1.0)])
    assert devtrace.idle_pct(tr, 0.0, 1.0) is None


@pytest.mark.parametrize("name,want", [
    ("all-reduce.12", True), ("all-reduce-start.3", True),
    ("all-reduce-done", True), ("all-gather.1", True),
    ("reduce-scatter.7", True), ("collective-permute-start.2", True),
    ("all-to-all.0", True), ("fusion.12", False), ("copy.3", False),
    ("convolution.1", False)])
def test_collective_classifier(name, want):
    assert devtrace.is_collective(name) is want


def test_collective_seconds():
    tr = _trace()
    t = devtrace.op_seconds(tr, 0.0, 4.0, devtrace.is_collective)
    assert t == pytest.approx(0.5)          # 1 s on one of two devices


def test_breakdown_names_ops_and_gaps():
    tr = _trace()
    bd = devtrace.breakdown(tr, 0.0, 4.0, tr.host, top=3)
    names = dict(bd["device_ops"])
    assert names["fusion"] == pytest.approx(1.5)
    assert names["all-reduce"] == pytest.approx(0.5)
    gaps = bd["idle_gaps"]
    assert len(gaps) == 3 and gaps[0][1] == pytest.approx(1.5)
    # device 1's gap [2.5, 4] has its midpoint outside bench.fit
    assert gaps[0][0] == "bench.window"


def test_kmv_work_counted_by_hand():
    kmv = spec.layer_reader("kmv_roofline.fit")
    # krr-msd: m=463715, n=90, r=s*b=128
    flops, nbytes = kmv.work(463715, 90, 128)
    assert flops == 2 * 463715 * 128 * 91 == 10_802_704_640
    assert nbytes == 4 * (463715 * 90 + 128 * 90 + 463715) == 168_838_340
    t, which = kmv.bound(463715, 90, 128, {"flops_bf16": 197e12,
                                            "hbm_bytes_per_s": 819e9})
    assert which == "bytes"
    assert t == pytest.approx(168_838_340 / 819e9)


def test_peaks_table_refuses_unknown_device():
    row = spec.peaks("TPU v5 lite")
    assert row["flops_bf16"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.UnknownDevice):
        spec.peaks("TPU v9 imaginary")


def test_every_cell_resolves_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert bench["command"] == ["python3", "bench/run.py"]
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], bench)
        assert cell.kind in ("fit", "serve")
        assert cell.limits["checks"] and cell.limits["n_check"] >= 1
        assert any(m["name"] != "setup_s" for m in cell.end_to_end)
        assert cell.per_layer
        for m in cell.per_layer:
            assert hasattr(spec.layer_reader(m["name"]), "read")
    for c in bench["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert conf["reduced"] == c["reduced"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         bench["workloads"][0]["name"],
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_the_cpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_command_refuses_sanitize_mode():
    p = _run(ROOT, {"REPRO_SANITIZE": "1"})
    assert p.returncode != 0 and "{" not in p.stdout


def test_command_fails_without_the_program(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_reducers_on_a_recorded_chip_trace():
    tr = devtrace.load(str(DATA / "small_trace.xplane.pb"))
    assert tr.ops, "the recorded trace has device operations"
    lo, hi = tr.annotation("bench.window")
    busy = devtrace.busy_s(tr, lo, hi)
    assert 0 < busy < hi - lo
    assert 0 < devtrace.idle_pct(tr, lo, hi) < 100
    bd = devtrace.breakdown(tr, lo, hi, [e for e in tr.host
                                         if e[0].startswith("bench.")])
    assert bd["device_ops"] and bd["idle_gaps"]
    assert sum(v for _, v in bd["device_ops"]) <= busy * 1.01
    assert devtrace.op_seconds(tr, lo, hi, devtrace.is_collective) == 0
    assert devtrace.module_seconds(tr, "kmv") > 0


def test_hlo_text_names():
    assert devtrace.op_name(
        "%copy-start.2 = (f32[128,90]{0,1:T(8,128)S(1)}) copy-start(%B.1)"
    ) == "copy-start.2"
    assert devtrace.op_name("fusion.3") == "fusion.3"
    assert devtrace.op_family("copy-start.2") == "copy-start"
