#!/usr/bin/env python3
"""The program's own marks in a profiler trace: each device op's named
scope (``repro.obs.scopes``) and the ``Server``'s host spans (``serve.*``).

    python3 bench/scope_trace.py <.xplane.pb> [--json out.json]

``load`` reads an ``.xplane.pb`` as ``trace_reduce.load`` does, into a
:class:`ScopedTrace`: the same window, ops, programs and harness spans,
plus ``scopes[device]`` (one entry per op of ``ops[device]``: the innermost
scope named on the op's ``tf_op`` path, ``""`` where none is) and
``program`` (the ``serve.*`` spans, on the device ops' clock).  A program
without scopes reads ``""`` for every op, and a trace kept as JSON without
the two fields loads and reads as before.  The script prints, for each
served program, its device time per scope and what no scope covers, the
per-step times that the metric readers read (:func:`step_metrics`), and the
longest idle gaps by the span over them.

A reader of the program's scopes declares ``PROGRAM`` and ``SCOPES`` and
returns ``scope_ms(run.trace, PROGRAM, SCOPES)``; a new scope needs nothing
here.
"""

from __future__ import annotations

import json
import sys
from bisect import bisect_left
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"]

from bench import trace_reduce as tr  # noqa: E402

PROGRAM_PREFIX = "serve."
TF_OP = "tf_op"  # the stat of an op's metadata that holds its HLO op_name
PROGRAMS = ("jit_decode_step", "jit_prefill")


def scope_names() -> tuple:
    """The program's scope names; none where the program has no scopes."""
    try:
        from repro.obs.scopes import SCOPES
    except ImportError:
        return ()
    return SCOPES


def scope_of(op_name: str, names) -> str:
    """The innermost of ``names`` on an op's path, ``""`` where none is."""
    for part in reversed(op_name.split("/")):
        if part in names:
            return part
    return ""


@dataclass
class ScopedTrace(tr.Trace):
    scopes: dict = field(default_factory=dict)  # per device: the scope of each op
    program: list = field(default_factory=list)  # [name, start, duration] of serve.* spans

    def scope_ns(self, prefix: str) -> tuple[int, dict]:
        """(runs, {scope: device ns}) of the programs whose name starts with
        ``prefix``: the runs that lie wholly in the window, and the ops that
        start inside them, loops and branches left out (their bodies' ops
        are counted)."""
        runs, ns = 0, defaultdict(float)
        for run in self.runs(prefix):
            runs += 1
            for op, scope in run:
                ns[scope] += op[2]
        return runs, dict(ns)

    def runs(self, prefix: str):
        """Each run of the programs whose name starts with ``prefix`` that
        lies wholly in the window, as the (op, scope) pairs of the ops that
        start inside it, loops and branches left out."""
        for dev, events in self.modules.items():
            ops = self.ops.get(dev, [])
            names = self.scopes.get(dev) or [""] * len(ops)
            order = sorted(range(len(ops)), key=lambda i: ops[i][1])
            starts = [ops[i][1] for i in order]
            for name, start, dur in events:
                if name.startswith(prefix) and self._inside(start, dur):
                    yield [(ops[i], names[i])
                           for i in order[bisect_left(starts, start):bisect_left(starts, start + dur)]
                           if tr._family(ops[i][0]) not in tr.CONTAINERS]

    def program_gaps(self, n: int = 10) -> list:
        """The longest idle gaps of the first device, each named by the span
        that covers most of it: a ``serve.*`` span (kept whole, and taken
        over the harness's span it lies in where both cover as much), else
        the harness's span as ``idle_gaps`` names it, else ``host``."""
        if not self.ops:
            return []
        busy = tr._union(self._clipped(next(iter(self.ops.values()))))
        edges = [self.window[0]] + [x for iv in busy for x in iv] + [self.window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self._span_over(a, b), (b - a) * 1e-9] for a, b in gaps[:n]]

    def _span_over(self, a: float, b: float) -> str:
        spans = [(start, dur, name) for name, start, dur in self.program] + [
            (start, dur, name[len(tr.HOST_PREFIX):]) for name, start, dur in self.host
            if name != tr.WINDOW_SPAN]  # program spans first: they win a tie
        best, label = 0.0, "host"
        for start, dur, name in spans:
            over = min(b, start + dur) - max(a, start)
            if over > best:
                best, label = over, name
        return label

    def to_json(self) -> str:
        d = json.loads(super().to_json())
        return json.dumps({**d, "scopes": self.scopes, "program": self.program})

    @classmethod
    def from_json(cls, text: str) -> "ScopedTrace":
        d = json.loads(text)
        return cls(tuple(d["window"]), d["ops"], d["modules"], d["host"],
                   d.get("scopes", {}), d.get("program", []))


def scope_ms(trace: ScopedTrace, program: str, scopes) -> float | None:
    """Device ms per run of ``program`` in the ops under ``scopes``, or None
    where the trace holds no run of it or no op of it under any scope (a
    program without scopes)."""
    runs, ns = trace.scope_ns(program)
    if not runs or not any(v for s, v in ns.items() if s):
        return None
    return sum(ns.get(s, 0.0) for s in scopes) / runs * 1e-6


def readers(root: Path = ROOT) -> dict:
    """Every metric reader under ``root``, by metric name."""
    from bench.harness import reader

    return {p.stem: reader(p.stem, root)
            for p in sorted((root / "bench" / "metrics").glob("*.py"))
            if not p.stem.startswith("_")}


def step_metrics(root: Path = ROOT) -> dict:
    """{metric: (program, scopes)} of the readers that read the program's
    scopes (those that declare ``SCOPES``)."""
    return {name: (mod.PROGRAM, mod.SCOPES) for name, mod in readers(root).items()
            if hasattr(mod, "SCOPES")}


def _messages():
    """Message classes for the part of an XSpace read here (the fields of
    tsl/profiler/protobuf/xplane.proto by number; the rest is skipped)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    proto = descriptor_pb2.FileDescriptorProto(name="bench_xspace.proto", package="bx",
                                               syntax="proto3")
    schema = {
        "XSpace": [("planes", 1, many, ".bx.XPlane")],
        "XPlane": [("name", 2, one, F.TYPE_STRING), ("lines", 3, many, ".bx.XLine"),
                   ("event_metadata", 4, many, ".bx.EventMetadataEntry"),
                   ("stat_metadata", 5, many, ".bx.StatMetadataEntry")],
        "EventMetadataEntry": [("key", 1, one, F.TYPE_INT64),
                               ("value", 2, one, ".bx.XEventMetadata")],
        "StatMetadataEntry": [("key", 1, one, F.TYPE_INT64),
                              ("value", 2, one, ".bx.XStatMetadata")],
        "XLine": [("name", 2, one, F.TYPE_STRING), ("timestamp_ns", 3, one, F.TYPE_INT64),
                  ("events", 4, many, ".bx.XEvent")],
        "XEvent": [("metadata_id", 1, one, F.TYPE_INT64), ("offset_ps", 2, one, F.TYPE_INT64),
                   ("duration_ps", 3, one, F.TYPE_INT64)],
        "XEventMetadata": [("name", 2, one, F.TYPE_STRING), ("stats", 5, many, ".bx.XStat")],
        "XStat": [("metadata_id", 1, one, F.TYPE_INT64), ("str_value", 5, one, F.TYPE_STRING),
                  ("ref_value", 7, one, F.TYPE_UINT64)],
        "XStatMetadata": [("name", 2, one, F.TYPE_STRING)],
    }
    for msg, fields in schema.items():
        m = proto.message_type.add(name=msg)
        for name, number, label, kind in fields:
            f = m.field.add(name=name, number=number, label=label)
            if isinstance(kind, str):
                f.type, f.type_name = F.TYPE_MESSAGE, kind
            else:
                f.type = kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bx.XSpace"))


def _op_scopes(path: Path, window, names) -> dict:
    """Per device plane, the scope of each op the window keeps, in the order
    (and by the times) that ``jax.profiler.ProfileData`` reads them."""
    space = _messages()()
    space.ParseFromString(Path(path).read_bytes())
    lo, hi = window
    out = {}
    for plane in space.planes:
        if not tr.is_device_plane(plane.name):
            continue
        stat = {e.key: e.value.name for e in plane.stat_metadata}
        op_name = {}
        for e in plane.event_metadata:
            for s in e.value.stats:
                if stat.get(s.metadata_id) == TF_OP:
                    op_name[e.key] = s.str_value or stat.get(s.ref_value, "")
        for line in plane.lines:
            if line.name == tr.OPS_LINE:
                out[plane.name] = [
                    scope_of(op_name.get(e.metadata_id, "").rstrip(":"), names)
                    for e in line.events
                    for start in [line.timestamp_ns + e.offset_ps // 1000]
                    if start < hi and start + e.duration_ps // 1000 > lo]
    return out


def load(path: Path, kernels=()) -> ScopedTrace:
    """``trace_reduce.load`` of the file, with each op's scope and the
    program's host spans."""
    from jax.profiler import ProfileData

    base = tr.load(path, kernels)
    scopes = _op_scopes(path, base.window, scope_names())
    for dev, ops in base.ops.items():
        if len(scopes.get(dev, ())) != len(ops):
            raise ValueError(f"{path}: {dev} has {len(ops)} ops in the window, "
                             f"{len(scopes.get(dev, ()))} read with their metadata")
    lo, hi = base.window
    program = [[e.name, e.start_ns, e.duration_ns]
               for plane in ProfileData.from_file(str(path)).planes
               if plane.name.startswith("/host:") for line in plane.lines
               for e in line.events if e.name.startswith(PROGRAM_PREFIX)
               and e.start_ns < hi and e.start_ns + e.duration_ns > lo]
    return ScopedTrace(base.window, base.ops, base.modules, base.host, scopes, program)


def report(t: ScopedTrace) -> dict:
    """What the script prints: for each served program, its runs, device ms
    per run, each scope's share of its ops' time, the ops outside every
    scope over 1% of it, the buckets' sum against the programs' own time;
    the per-step times; the longest idle gaps."""
    out = {}
    for prog in PROGRAMS:
        runs, ns = t.scope_ns(prog)
        if not runs:
            continue
        total, (_, module_ns) = sum(ns.values()), t.module_runs(prog)
        unscoped = defaultdict(float)
        for run in t.runs(prog):
            for op, scope in run:
                if not scope:
                    unscoped[tr._family(op[0])] += op[2]
        out[prog] = {
            "runs": runs, "ms_per_run": total / runs * 1e-6,
            "share_pct": {k: 100 * v / total for k, v in sorted(ns.items(), key=lambda kv: -kv[1])},
            "unscoped_over_1pct": {k: 100 * v / total for k, v in
                                   sorted(unscoped.items(), key=lambda kv: -kv[1])
                                   if v > 0.01 * total},
            "ops_over_module_pct": 100 * total / module_ns,
        }
    out["step_ms"] = {name: scope_ms(t, *spec) for name, spec in step_metrics().items()}
    out["gaps"] = t.program_gaps(10)
    return out


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane", type=Path)
    ap.add_argument("--json", type=Path, help="also keep the scoped trace here")
    args = ap.parse_args()
    declared = {getattr(mod, "KERNEL", None) for mod in readers().values()}
    trace = load(args.xplane, tuple(sorted(declared - {None})))
    if args.json:
        args.json.write_text(trace.to_json())
    print(json.dumps(report(trace), indent=1))
