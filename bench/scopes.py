"""Which phase of the round each device operation of a traced run belongs to.

The program runs each phase of a solver round under a ``jax.named_scope``
whose names it lists in ``repro.obs.SCOPES``.  The profiler keeps an op's
scope path in the ``tf_op`` stat of the op's event metadata, per device
plane of the xplane (``jit(f)/while/body/kmv/dot_general:``);
``jax.profiler.ProfileData`` does not show metadata stats, so ``decode``
reads that part of the file with ``google.protobuf`` and a minimal
descriptor of ``XSpace`` that leaves ``XPlane.lines`` (the events, which
``devtrace.load`` has already read) out.

An op is named by its HLO text, unique within one program only, so an
executed op is looked up by the program that ran it: the ``XLA Modules``
interval that encloses it (``jit_f(<program_id>)``) and the metadata's
``program_id``.  It belongs to the innermost scope name on its path;
with no ``tf_op``, or none of the names, it is ``unscoped``: its owner
is not guessed.

``phases`` gives each (owner, op family) the device seconds in which
that op was the innermost one running (an enclosing ``while`` owns only
the time none of its body ops covers: the loop's own overhead), inside
the program's ``repro.*`` annotations of one name, averaged over the
devices; the sum is the device busy time there, which
``Phases.accounted`` checks to 1%.

A profile can drop device events (on a TPU v5e, one traced run of
``ksvm-covtype.fit`` in five kept 70% of them); the enclosing ``while``
then covers the hole and the owners' sum still equals the busy time.  Fits of one cell run the same
program for the same rounds, so ``solve_phases`` also checks that each
traced solve shows as many device ops per round, to 1%.

The four readers that use this cache one decode per run on the context.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench import devtrace

try:
    from repro.obs.scopes import SCOPES, owner
except ImportError:          # a program without named scopes
    SCOPES = ()

    def owner(path: str):
        return None

UNSCOPED = "unscoped"
PROGRAM_ID = re.compile(r"\((\d+)\)$")
TOLERANCE = 0.01             # scoped + unscoped vs busy


def _classes():
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xplane_min.proto",
                                            package="bench_xplane_min",
                                            syntax="proto3")

    def add(msg, name, number, kind, repeated=False, type_name=None):
        f = msg.field.add(name=name, number=number, type=kind,
                          label=(F.LABEL_REPEATED if repeated
                                 else F.LABEL_OPTIONAL))
        if type_name:
            f.type_name = ".bench_xplane_min." + type_name

    stat = fd.message_type.add(name="XStat")
    add(stat, "metadata_id", 1, F.TYPE_INT64)
    add(stat, "double_value", 2, F.TYPE_DOUBLE)
    add(stat, "uint64_value", 3, F.TYPE_UINT64)
    add(stat, "int64_value", 4, F.TYPE_INT64)
    add(stat, "str_value", 5, F.TYPE_STRING)
    add(stat, "bytes_value", 6, F.TYPE_BYTES)
    add(stat, "ref_value", 7, F.TYPE_UINT64)
    em = fd.message_type.add(name="XEventMetadata")
    add(em, "id", 1, F.TYPE_INT64)
    add(em, "name", 2, F.TYPE_STRING)
    add(em, "stats", 5, F.TYPE_MESSAGE, True, "XStat")
    sm = fd.message_type.add(name="XStatMetadata")
    add(sm, "id", 1, F.TYPE_INT64)
    add(sm, "name", 2, F.TYPE_STRING)
    plane = fd.message_type.add(name="XPlane")
    add(plane, "name", 2, F.TYPE_STRING)
    for field, number, value in (("event_metadata", 4, "XEventMetadata"),
                                 ("stat_metadata", 5, "XStatMetadata")):
        entry = plane.nested_type.add(
            name="".join(w.title() for w in field.split("_")) + "Entry")
        entry.options.map_entry = True
        add(entry, "key", 1, F.TYPE_INT64)
        add(entry, "value", 2, F.TYPE_MESSAGE, type_name=value)
        add(plane, field, number, F.TYPE_MESSAGE, True,
            f"XPlane.{entry.name}")
    space = fd.message_type.add(name="XSpace")
    add(space, "planes", 1, F.TYPE_MESSAGE, True, "XPlane")
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane_min.XSpace"))


def decode(path: str) -> Dict[str, Dict[Tuple[int, str], str]]:
    """Per device plane: (program_id, op name) -> the op's ``tf_op``."""
    space = _classes()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out: Dict[str, Dict[Tuple[int, str], str]] = {}
    for plane in space.planes:
        if not devtrace.DEVICE_PLANE.match(plane.name):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        table: Dict[Tuple[int, str], str] = {}
        for meta in plane.event_metadata.values():
            stats = {names.get(s.metadata_id): s for s in meta.stats}
            pid, tf = stats.get("program_id"), stats.get("tf_op")
            if pid is None or tf is None:
                continue
            # a string stat is held inline or as a stat metadata name
            text = tf.str_value or names.get(tf.ref_value, "")
            if text:
                table.setdefault(
                    (pid.int64_value or pid.uint64_value,
                     devtrace.op_name(meta.name)), text)
        out[plane.name] = table
    return out


def exclusive(events) -> Dict[object, float]:
    """Seconds in which each event's key is the innermost one running:
    of the events that cover an instant, the one that started last
    (for equal starts, the shorter).  ``events``: (key, start, end)."""
    out: Dict[object, float] = defaultdict(float)
    stack: List[Tuple[float, object]] = []        # (end, key)
    t = None
    for key, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and t < a:
            end, k = stack[-1]
            if end <= t:
                stack.pop()
                continue
            step = min(end, a)
            out[k] += step - t
            t = step
            if end <= a:
                stack.pop()
        t = a if t is None or t < a else t
        stack.append((b, key))
    while stack:
        end, k = stack.pop()
        if end > t:
            out[k] += end - t
            t = end
    return out


@dataclasses.dataclass
class Phases:
    """Device seconds inside the annotations named ``span``, averaged
    over the devices: ``by`` maps (owner, op family) to seconds; ``busy``
    is the union of every op there."""
    span: str
    n_spans: int
    by: Dict[Tuple[str, str], float]
    busy: float
    counts: List[int] = dataclasses.field(default_factory=list)
    paths: Dict[str, set] = dataclasses.field(default_factory=dict)
    rounds: int = 0              # set by solve_phases

    def seconds(self, *owners: str) -> float:
        return sum(v for (o, _), v in self.by.items() if o in owners)

    def owners(self) -> Dict[str, float]:
        out: Dict[str, float] = defaultdict(float)
        for (o, _), v in self.by.items():
            out[o] += v
        return dict(out)

    def families(self, who: str) -> List[Tuple[str, float]]:
        return sorted(((f, v) for (o, f), v in self.by.items()
                       if o == who), key=lambda kv: -kv[1])

    def owners_of(self, family: str) -> List[Tuple[str, float]]:
        return sorted(((o, v) for (o, f), v in self.by.items()
                       if f == family), key=lambda kv: -kv[1])

    def accounted(self) -> Optional[str]:
        """None when the owners' seconds add up to the busy time to 1%,
        else why not."""
        total = sum(self.by.values())
        if self.busy <= 0 or abs(total - self.busy) > TOLERANCE * self.busy:
            return (f"scoped + unscoped {total:.6f} s != busy "
                    f"{self.busy:.6f} s inside {self.span}")
        return None


def spans(trace, name: str, lo: float, hi: float) -> List[Tuple[float, float]]:
    """(start, end) of the host annotations called ``name`` in [lo, hi]."""
    return sorted((a, b) for n, a, b in trace.host
                  if n == name and a >= lo and b <= hi)


def _owner_of(table, modules, starts, name, fam, t, cache, paths):
    """The owner of op ``name`` that ran at ``t``, by the program whose
    ``XLA Modules`` interval encloses it; its ``tf_op`` joins ``paths``
    of its family ("" for none)."""
    i = bisect.bisect_right(starts, t) - 1
    pid = None
    if i >= 0 and modules[i][1] <= t <= modules[i][2]:
        m = PROGRAM_ID.search(modules[i][0])
        pid = int(m.group(1)) if m else None
    key = (pid, name)
    got = cache.get(key)
    if got is None:
        tf = table.get(key, "")
        paths[fam].add(tf)
        got = cache[key] = (owner(tf) if tf else None) or UNSCOPED
    return got


def phases(trace, meta, span: str, lo: float, hi: float) -> Phases:
    """The owners' device seconds inside the ``span`` annotations."""
    iv = spans(trace, span, lo, hi)
    by: Dict[Tuple[str, str], float] = defaultdict(float)
    n = max(1, len(trace.ops))
    counts = [0] * len(iv)
    family: Dict[str, str] = {}
    paths: Dict[str, set] = defaultdict(set)
    for dev, ops in trace.ops.items():
        table = meta.get(dev, {})
        modules = sorted(trace.modules.get(dev, []), key=lambda e: e[1])
        mstarts = [e[1] for e in modules]
        ops = sorted(ops, key=lambda e: e[1])
        starts = [e[1] for e in ops]
        longest = max((e - s for _, s, e in ops), default=0.0)
        cache: Dict[Tuple[Optional[int], str], str] = {}
        for n_span, (a, b) in enumerate(iv):
            counts[n_span] += bisect.bisect_left(starts, b) - \
                bisect.bisect_left(starts, a)
            # an op that runs into the span started at most one longest
            # op before it (an enclosing while may be that long)
            i = bisect.bisect_left(starts, a - longest)
            j = bisect.bisect_left(starts, b)
            inside = []
            for name, s, e in ops[i:j]:
                if e <= a or s >= b:
                    continue
                fam = family.get(name)
                if fam is None:
                    fam = family[name] = devtrace.op_family(name)
                who = _owner_of(table, modules, mstarts, name, fam, s,
                                cache, paths)
                inside.append(((who, fam), max(s, a), min(e, b)))
            for k, v in exclusive(inside).items():
                by[k] += v / n
    # the busy time is read from the whole op lists, apart from the
    # windowing above, so the check below also covers that
    busy = sum(devtrace.busy_s(trace, a, b) for a, b in iv)
    return Phases(span=span, n_spans=len(iv), by=dict(by), busy=busy,
                  counts=counts, paths=dict(paths))


def solve_phases(ctx) -> Optional[Phases]:
    """The ``repro.solve`` phases of the traced run, decoded once per
    run and kept on ``ctx``, with the rounds the traced fits ran; None
    (with a note) when the program names no scopes or spans, the trace
    holds another number of solves than the traced fits, or the
    accounting does not add up."""
    if hasattr(ctx, "solve_phases"):
        return ctx.solve_phases
    ctx.solve_phases = None
    lo, hi = ctx.window
    if not SCOPES:
        ctx.notes.append("scopes: the program names no scopes")
        return None
    if not spans(ctx.trace, "repro.solve", lo, hi):
        ctx.notes.append("scopes: no repro.solve annotations in the window")
        return None
    from bench.run import CACHE     # where the traced run's profile is
    meta = decode(devtrace.find_xplane(str(CACHE / "trace")))
    ph = phases(ctx.trace, meta, "repro.solve", lo, hi)
    fits = [f for f in ctx.driver.fits
            if any(name == "solve" for name, _, _ in f.spans)]
    if len(fits) != ph.n_spans:
        ctx.notes.append(f"scopes: {ph.n_spans} repro.solve annotations "
                         f"in the trace, {len(fits)} traced fits")
        return None
    why = ph.accounted()
    per_round = [c / f.rounds for c, f in zip(ph.counts, fits)]
    if why is None and max(per_round) > (1 + TOLERANCE) * min(per_round):
        why = ("the profile dropped device events: "
               + ", ".join(f"{c} ops in {f.rounds} rounds"
                           for c, f in zip(ph.counts, fits)))
    if why is not None:
        ctx.notes.append(f"scopes: {why}")
        return None
    ph.rounds = sum(f.rounds for f in fits)
    ctx.solve_phases = ph
    return ph
