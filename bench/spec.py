"""Everything the benchmark reads from files, found by name.

* ``BENCHMARK.json`` at the root of the checkout: cells, metrics.
* ``bench/configs/<config>.json``: one deployment (data shape, problem,
  kernel, solver settings, the fit budget).
* ``bench/traffic/<traffic>.json``: one traffic mix, read by the general
  driver of its ``kind`` (``fit`` or ``serve``).
* ``bench/limits/<cell>.json``: the limits of the numbers a cell's
  correctness check compares, with the readings they were set from.
* ``bench/layers/<metric>.py``: the reader of one per-layer metric.
* ``bench/peaks.json``: peak rates keyed by ``device_kind``.

A later change adds a configuration, a mix, a cell or a metric by adding
files and entries; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class UnknownDevice(KeyError):
    """The peaks table has no row for this ``device_kind``."""


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def peaks(kind: str, table: Optional[dict] = None) -> dict:
    """The peak row of ``device_kind`` ``kind``; refuses an unknown kind."""
    table = table if table is not None else _json(BENCH / "peaks.json")
    rows = table["devices"]
    if kind not in rows:
        raise UnknownDevice(f"no peaks for device_kind {kind!r} in "
                            f"bench/peaks.json (have {sorted(rows)})")
    return rows[kind]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list          # metric entries of BENCHMARK.json
    per_layer: list

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    ws = metric.get("workloads")
    if ws is not None:
        return cell in ws
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench if bench is not None else _json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have "
                       f"{sorted(cells)})")
    w = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    e2e = [m for m in bench["end_to_end"]
           if m["name"] == "setup_s" or name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]),
                config=_json(ROOT / conf["file"]),
                traffic=_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                limits=_json(BENCH / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


def layer_reader(metric: str):
    """The module ``bench/layers/<metric>.py`` (loaded by path: metric
    names hold dots)."""
    path = BENCH / "layers" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_layer_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
