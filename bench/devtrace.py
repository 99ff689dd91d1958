"""Reduction of a profiler trace to the numbers the benchmark reports.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``load`` reads it with ``jax.profiler.ProfileData`` into plain interval
lists (seconds on the trace's clock):

* ``ops[device]``: the device's ``XLA Ops`` line, one interval per
  executed operation;
* ``modules[device]``: its ``XLA Modules`` line, one interval per
  executed program (``jit_<function>(<id>)``);
* ``host``: every event of every host line (``TraceAnnotation`` spans
  the benchmark writes around its own phases among them).

Everything else here is arithmetic on those lists, so the tests can run
it on synthetic intervals and on a small recorded trace.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Interval = Tuple[str, float, float]          # (name, start_s, end_s)

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# operations that contain other operations on the same line: counting
# them as time of their own would count their bodies twice
CONTAINERS = re.compile(r"^(while|conditional|call)(\.|$)")
HLO_NAME = re.compile(r"^%?([\w.\-]+)\s*=")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast)(-start|-done)?([.\-_]|$)")


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Interval]]
    host: List[Interval]
    modules: Dict[str, List[Interval]] = dataclasses.field(
        default_factory=dict)

    def annotation(self, name: str) -> Optional[Tuple[float, float]]:
        """(start, end) of the first host event called ``name``."""
        for n, t0, t1 in self.host:
            if n == name:
                return t0, t1
        return None


def op_name(text: str) -> str:
    """A TPU trace names an operation by its HLO text,
    ``%fusion.12 = f32[...] fusion(...)``: keep ``fusion.12``."""
    m = HLO_NAME.match(text)
    return m.group(1) if m else text


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[str, List[Interval]] = {}
    modules: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [
                        (op_name(e.name), e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events)
    return Trace(ops=ops, host=host, modules=modules)


def clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(n, max(a, lo), min(b, hi)) for n, a, b in iv
            if b > lo and a < hi]


def merged(iv: List[Interval]) -> List[Tuple[float, float]]:
    """The union of the intervals as sorted, disjoint (start, end)."""
    out: List[List[float]] = []
    for _, a, b in sorted(iv, key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union_s(iv: List[Interval]) -> float:
    return sum(b - a for a, b in merged(iv))


def busy_s(trace: Trace, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which some operation ran, averaged over
    the devices in the trace."""
    if not trace.ops:
        return 0.0
    return sum(union_s(clip(iv, lo, hi)) for iv in trace.ops.values()) \
        / len(trace.ops)


def idle_pct(trace: Trace, lo: float, hi: float) -> Optional[float]:
    """100 * (1 - busy / window), or None with no device events."""
    if not any(clip(iv, lo, hi) for iv in trace.ops.values()):
        return None
    return 100.0 * (1.0 - busy_s(trace, lo, hi) / (hi - lo))


def op_seconds(trace: Trace, lo: float, hi: float, match) -> float:
    """Seconds of [lo, hi] in operations whose name ``match`` accepts,
    union per device, averaged over the devices."""
    if not trace.ops:
        return 0.0
    return sum(union_s([e for e in clip(iv, lo, hi) if match(e[0])])
               for iv in trace.ops.values()) / len(trace.ops)


def module_seconds(trace: Trace, function: str) -> float:
    """Device seconds of the programs jitted from ``function``, summed
    over their executions, averaged over the devices."""
    prefix = f"jit_{function}("
    if not trace.modules:
        return 0.0
    return sum(b - a for iv in trace.modules.values()
               for name, a, b in iv if name.startswith(prefix)) \
        / len(trace.modules)


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(name))


def op_family(name: str) -> str:
    """``fusion.123`` -> ``fusion``: the name without its instance."""
    return re.sub(r"[.\-_]\d+$", "", name)


def breakdown(trace: Trace, lo: float, hi: float,
              labels: List[Interval], top: int = 10) -> dict:
    """The device operations that took most time (by family, averaged
    over devices) and the longest idle gaps, each gap named by the
    benchmark span (``labels``) that covers its midpoint."""
    n = max(1, len(trace.ops))
    per = defaultdict(float)
    gaps = []
    for iv in trace.ops.values():
        for name, a, b in clip(iv, lo, hi):
            if not CONTAINERS.match(name):
                per[op_family(name)] += (b - a) / n
        edge = lo
        for a, b in merged(clip(iv, lo, hi)) + [(hi, hi)]:
            if a > edge:
                gaps.append((edge, a))
            edge = max(edge, b)
    by_length = sorted(labels, key=lambda x: x[2] - x[1])
    idle = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (a + b)
        label = next((nm for nm, s, e in by_length if s <= mid <= e),
                     "outside_spans")
        idle.append([label, b - a])
    return {
        "device_ops": sorted(([k, v] for k, v in per.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": idle,
    }
