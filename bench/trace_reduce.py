"""From a profiler trace to the numbers the metric readers take.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps,
for the traced window only: every device op and every program (XLA module)
run on each TPU, and the harness's own host spans (``bench.*``).  The
reductions work on that small record, which ``to_json``/``from_json`` keep,
so they can be checked on a committed trace.  Times are nanoseconds on the
trace's clock; the window is the host span ``bench.traced``.
"""

from __future__ import annotations

import json
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_SPAN = "bench.traced"
HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTAINERS = {"while", "conditional", "call"}  # ops whose bodies are ops of their own


@dataclass
class Trace:
    window: tuple[float, float]
    # per device: [name, start, duration, kernel] (kernel: the Pallas
    # kernel's name where the op is one, else "")
    ops: dict[str, list] = field(default_factory=dict)
    modules: dict[str, list] = field(default_factory=dict)  # [name, start, duration]
    host: list = field(default_factory=list)  # [name, start, duration]

    # -- reductions --------------------------------------------------------

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def busy_ns(self, device: str) -> float:
        """Union of the device's op intervals inside the window."""
        return sum(b - a for a, b in _union(self._clipped(self.ops[device])))

    def mean_busy_ns(self) -> float:
        return sum(self.busy_ns(d) for d in self.ops) / max(len(self.ops), 1)

    def module_runs(self, prefix: str) -> tuple[int, float]:
        """(runs, summed device ns) of the programs whose name starts with
        ``prefix``, on all devices, counting only runs that lie wholly in
        the window."""
        n, total = 0, 0.0
        for events in self.modules.values():
            for name, start, dur in events:
                if name.startswith(prefix) and self._inside(start, dur):
                    n, total = n + 1, total + dur
        return n, total

    def kernel_runs(self, kernel: str) -> tuple[int, float]:
        """(calls, summed device ns) of a Pallas kernel by its name."""
        n, total = 0, 0.0
        for events in self.ops.values():
            for _, start, dur, k in events:
                if k == kernel and self._inside(start, dur):
                    n, total = n + 1, total + dur
        return n, total

    def top_ops(self, n: int = 10) -> list:
        """The ops that took most device time, seconds summed over runs
        (an op's name with its numeric suffix dropped).  Loops and
        branches are left out: the ops of their bodies are listed."""
        total: dict[str, float] = defaultdict(float)
        for events in self.ops.values():
            for name, a, b in ((e[0], e[1], e[1] + e[2]) for e in events):
                lo, hi = max(a, self.window[0]), min(b, self.window[1])
                if hi > lo and _family(name) not in CONTAINERS:
                    total[_family(name)] += (hi - lo) * 1e-9
        return sorted(([k, v] for k, v in total.items()), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10) -> list:
        """The longest idle gaps of the first device, each named by the
        host span that covers most of it (``bench.dispatch``,
        ``bench.readback``, ``bench.prepare``; ``host`` where none does)."""
        if not self.ops:
            return []
        busy = _union(self._clipped(next(iter(self.ops.values()))))
        edges = [self.window[0]] + [x for iv in busy for x in iv] + [self.window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self._host_during(a, b), (b - a) * 1e-9] for a, b in gaps[:n]]

    # -- helpers -------------------------------------------------------------

    def _inside(self, start: float, dur: float) -> bool:
        return start >= self.window[0] and start + dur <= self.window[1]

    def _clipped(self, events) -> list:
        lo, hi = self.window
        return [(max(e[1], lo), min(e[1] + e[2], hi)) for e in events
                if e[1] < hi and e[1] + e[2] > lo]

    def _host_during(self, a: float, b: float) -> str:
        best, label = 0.0, "host"
        for name, start, dur in self.host:
            if name == WINDOW_SPAN:
                continue
            over = min(b, start + dur) - max(a, start)
            if over > best:
                best, label = over, name[len(HOST_PREFIX):]
        return label

    # -- storage ----------------------------------------------------------------

    def to_json(self) -> str:
        return json.dumps({"window": self.window, "ops": self.ops,
                           "modules": self.modules, "host": self.host})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls(tuple(d["window"]), d["ops"], d["modules"], d["host"])


def _family(name: str) -> str:
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def _union(intervals) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def short_name(name: str) -> str:
    """An op's HLO name: device events may carry the whole instruction
    (``%fusion.90 = bf16[...] fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%") if name.startswith("%") else name


def _kernel(name: str, kernels) -> str:
    """A Pallas kernel's custom call is named after the kernel
    (``decode_attention.1``)."""
    family = _family(name)
    return family if family in kernels else ""


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and name[len("/device:TPU:"):].isdigit()


def load(path: Path, kernels=()) -> Trace:
    """Read an ``.xplane.pb``; ``kernels`` are the Pallas kernel names to
    tell apart among the ops."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    host, ops, modules = [], {}, {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns] for e in line.events
                         if e.name.startswith(HOST_PREFIX)]
        elif is_device_plane(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [
                        [n, e.start_ns, e.duration_ns, _kernel(n, kernels)]
                        for e in line.events for n in [short_name(e.name)]]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [[e.name, e.start_ns, e.duration_ns]
                                           for e in line.events]
    spans = [h for h in host if h[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span")
    window = (spans[0][1], spans[0][1] + spans[0][2])
    lo, hi = window
    keep = lambda evs: [e for e in evs if e[1] < hi and e[1] + e[2] > lo]
    return Trace(window, {d: keep(v) for d, v in ops.items()},
                 {d: keep(v) for d, v in modules.items()}, keep(host))


def dump(path: Path, limit: int = 8) -> None:
    """Print what a trace holds: planes, lines, event counts and samples with
    their stats (for reading one trace by hand)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name}: {[(l.name, len(list(l.events))) for l in lines]}")
        for line in lines:
            for i, e in enumerate(line.events):
                if i >= limit:
                    break
                print(f"   {line.name} | {e.name[:160]} | {e.start_ns} | {e.duration_ns} | "
                      f"{ {k: str(v)[:80] for k, v in e.stats} }")


if __name__ == "__main__":
    import sys

    dump(Path(sys.argv[1]), int(sys.argv[2]) if len(sys.argv) > 2 else 8)
