"""Reduce a profiler trace to device operations, host spans and idle time.

A ``--trace 1`` run records one short window with ``jax.profiler``; the
trace (``*.xplane.pb``) holds one plane per TPU (``/device:TPU:<n>``),
whose ``XLA Ops`` line has one event per device operation and whose
``XLA Modules`` line has one event per program run, and the host plane,
which holds the benchmark's own ``bench.*`` spans (``TraceAnnotation``).
Times are nanoseconds on one clock for all planes.

This module turns that into plain records (:class:`Trace`) and computes
what every reader needs from them: the busy time of a device (the union
of its operations' intervals within the window), its idle gaps and what
the host was doing in each, and the sums of the operations a predicate
selects. The same reduction runs on a recorded trace in the tests.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from collections import defaultdict
from dataclasses import asdict, dataclass, field

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclass
class Op:
    device: int
    name: str
    start_ns: float
    dur_ns: float
    category: str = ""
    long_name: str = ""
    program: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def text(self) -> str:
        """Everything an operation is known by, lower-cased, for matching."""
        return " ".join((self.name, self.category, self.long_name)).lower()


@dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclass
class Trace:
    ops: list = field(default_factory=list)        # [Op]
    modules: list = field(default_factory=list)    # [Op] (program runs)
    spans: list = field(default_factory=list)      # [Span]

    @property
    def devices(self) -> list[int]:
        return sorted({o.device for o in self.ops}
                      | {m.device for m in self.modules})

    def window(self) -> tuple[float, float]:
        """The traced window: from the first benchmark span's start to the
        last one's end."""
        if not self.spans:
            raise ValueError("the trace holds no benchmark spans")
        return (min(s.start_ns for s in self.spans),
                max(s.end_ns for s in self.spans))

    # ---- (de)serialisation of a recorded trace ------------------------

    def to_json(self) -> dict:
        return {"ops": [asdict(o) for o in self.ops],
                "modules": [asdict(m) for m in self.modules],
                "spans": [asdict(s) for s in self.spans]}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(ops=[Op(**o) for o in d["ops"]],
                   modules=[Op(**m) for m in d["modules"]],
                   spans=[Span(**s) for s in d["spans"]])

    def save(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump(self.to_json(), f)

    @classmethod
    def load(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            tr = cls.from_json(json.load(f))
        attribute_programs(tr)
        return tr


def _stats(ev) -> dict:
    out = {}
    for item in ev.stats:
        k, v = item
        out[k] = v
    return out


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def read_xplane(path: str) -> Trace:
    """Read a profiler trace file into a :class:`Trace`."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                dest = tr.ops if line.name == OPS_LINE else tr.modules
                for ev in line.events:
                    st = _stats(ev)
                    dest.append(Op(
                        device=dev, name=ev.name, start_ns=ev.start_ns,
                        dur_ns=ev.duration_ns,
                        category=str(st.get("hlo_category", "")),
                        long_name=str(st.get("long_name",
                                             st.get("tf_op", "")))))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        tr.spans.append(Span(ev.name[len(SPAN_PREFIX):],
                                             ev.start_ns,
                                             ev.start_ns + ev.duration_ns))
    attribute_programs(tr)
    return tr


def attribute_programs(tr: Trace) -> None:
    """Name each operation's program: the module run that contains it."""
    by_dev = defaultdict(list)
    for m in tr.modules:
        by_dev[m.device].append(m)
    for mods in by_dev.values():
        mods.sort(key=lambda m: m.start_ns)
    for op in tr.ops:
        for m in by_dev.get(op.device, ()):
            if m.start_ns <= op.start_ns < m.end_ns:
                op.program = m.name
                break


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def busy_intervals(tr: Trace, device: int) -> list[tuple[float, float]]:
    """Merged intervals in which an operation ran on ``device``, within
    the window. Where a device line lists no operations, its program runs
    stand in."""
    lo, hi = tr.window()
    evs = [o for o in tr.ops if o.device == device] or \
        [m for m in tr.modules if m.device == device]
    iv = sorted(filter(None, (_clip(o.start_ns, o.end_ns, lo, hi)
                              for o in evs)))
    merged: list[list[float]] = []
    for a, b in iv:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_s(tr: Trace, device: int) -> float:
    return sum(b - a for a, b in busy_intervals(tr, device)) / 1e9


def window_s(tr: Trace) -> float:
    lo, hi = tr.window()
    return (hi - lo) / 1e9


def idle_gaps(tr: Trace, device: int) -> list[tuple[str, float]]:
    """Every idle gap of ``device`` in the window, longest first, each
    named by the benchmark span the host was in at the gap's middle."""
    lo, hi = tr.window()
    busy = busy_intervals(tr, device)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            mid = (a + b) / 2
            host = next((s.name for s in tr.spans
                         if s.start_ns <= mid < s.end_ns), "none")
            gaps.append((host, (b - a) / 1e9))
    return sorted(gaps, key=lambda g: -g[1])


def op_seconds(tr: Trace, device: int, pred) -> float:
    """Device seconds of the operations on ``device`` that ``pred``
    selects, within the window."""
    lo, hi = tr.window()
    total = 0.0
    for o in tr.ops:
        if o.device == device and pred(o):
            iv = _clip(o.start_ns, o.end_ns, lo, hi)
            if iv:
                total += iv[1] - iv[0]
    return total / 1e9


def top_ops(tr: Trace, device: int, k: int = 10) -> list[tuple[str, float]]:
    """The ``k`` operations of ``device`` that took most time, summed by
    name within their program."""
    acc: dict[str, float] = defaultdict(float)
    for o in tr.ops:
        if o.device == device:
            acc[f"{o.program}/{o.name}" if o.program else o.name] += \
                o.dur_ns / 1e9
    return sorted(acc.items(), key=lambda kv: -kv[1])[:k]
