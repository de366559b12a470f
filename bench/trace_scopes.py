"""The program's own ``fed.*`` device scopes and host spans in a profiler trace.

The program names its layers (``repro.utils.trace``): every device
operation traced inside a ``jax.named_scope("fed.<layer>")`` carries that
name in its ``op_name``, and the host plane holds its
``TraceAnnotation`` spans ``fed.<name>``. A TPU trace gives an
``XLA Ops`` event only its HLO text and timing, no ``tf_op``; the
``op_name`` is in the compiled HLO that each program leaves on the host
metadata plane (a ``Hlo Proto`` stat per module), looked up here by the
program run and instruction name.

:func:`read_xplane` reads a trace into a :class:`ScopedTrace`, a
:class:`bench.trace_reduce.Trace` whose operations also carry their scope
and which also holds the ``fed.*`` host spans, so every function of
``trace_reduce`` reads it as it reads a plain one. From it this module
computes the device time of each scope (the union of its operations'
intervals, so that a ``while`` and the operations of its body count
once), the busy time no scope covers, and the idle time inside the
program's host spans. Where a trace holds no benchmark spans (an
operator's own profile of a ``Session``), the window is the program's
host spans.

The benchmark's traced window reads its profile with :func:`read_xplane`,
and the per-layer readers of the program's layers
(``bench/metrics/local_sgd_ms_per_round.py``) take their numbers from it.
On a trace directory::

    python -m bench.trace_scopes TRACE_DIR [--rounds N]

prints the split of the first chip's busy time as JSON.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from collections import defaultdict
from dataclasses import asdict, dataclass, field

from bench import trace_reduce
from bench.trace_reduce import Op, Span, Trace

METADATA_PLANE = "/host:metadata"
#: the program's names: device scopes in an op's op_name, host spans (the
#: benchmark keeps its copy, so that a program change cannot move them)
PROGRAM_PREFIX = "fed."
LOCAL_SGD = ("fed.local_sgd",)
SERVER = ("fed.estimate", "fed.aggregate", "fed.history", "fed.policy")
EVAL = ("fed.eval",)


@dataclass
class ScopedOp(Op):
    scope: str = ""            # op_name: "jit(f)/vmap(fed.local_sgd)/…"


@dataclass
class ScopedTrace(Trace):
    program_spans: list = field(default_factory=list)   # [Span], "fed.*"

    def window(self) -> tuple[float, float]:
        """The benchmark's window where the trace has its spans, else the
        extent of the program's host spans."""
        if self.spans or not self.program_spans:
            return super().window()
        return (min(s.start_ns for s in self.program_spans),
                max(s.end_ns for s in self.program_spans))

    def to_json(self) -> dict:
        return {**super().to_json(),
                "program_spans": [asdict(s) for s in self.program_spans]}

    @classmethod
    def from_json(cls, d: dict) -> "ScopedTrace":
        return cls(ops=[ScopedOp(**o) for o in d["ops"]],
                   modules=[Op(**m) for m in d["modules"]],
                   spans=[Span(**s) for s in d["spans"]],
                   program_spans=[Span(**s)
                                  for s in d.get("program_spans", ())])


def read_xplane(path: str) -> ScopedTrace:
    """Read a profiler trace file into a :class:`ScopedTrace`."""
    from jax.profiler import ProfileData
    base = trace_reduce.read_xplane(path)
    tr = ScopedTrace(ops=[ScopedOp(**asdict(o)) for o in base.ops],
                     modules=base.modules, spans=base.spans)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_PREFIX):
                        tr.program_spans.append(Span(
                            ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns))
    with open(path, "rb") as f:
        attribute_scopes(tr, hlo_op_names(f.read()))
    return tr


# ---- op_name of each instruction, from the trace's compiled HLO ------------


def _fields(buf):
    """(field number, value) of each field of a serialized protobuf
    message; a length-delimited value is a memoryview of its bytes."""
    buf, i = memoryview(buf), 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _field(msg, number: int, default=b""):
    return next((v for k, v in _fields(msg) if k == number), default)


def hlo_op_names(xspace: bytes) -> dict[str, dict[str, str]]:
    """The op_name of every instruction of every program in a serialized
    trace: {module run name, ``jit_f(<id>)``: {instruction: op_name}},
    from the ``Hlo Proto`` each program leaves on the host metadata plane.

    Protobuf fields read (tsl ``xplane.proto``, xla ``hlo.proto``):
    XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map entry value
    2); XEventMetadata.name 2, .stats 5; XStat.bytes_value 6;
    HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1,
    .metadata 7; OpMetadata.op_name 2."""
    out: dict[str, dict[str, str]] = {}
    for k, plane in _fields(xspace):
        if k != 1 or bytes(_field(plane, 2)) != METADATA_PLANE.encode():
            continue
        for k2, entry in _fields(plane):
            if k2 != 4:
                continue
            meta = _field(entry, 2)
            name = bytes(_field(meta, 2)).decode()
            for k3, stat in _fields(meta):
                proto = _field(stat, 6) if k3 == 5 else b""
                if not len(proto):
                    continue
                names = out.setdefault(name, {})
                for k4, comp in _fields(_field(proto, 1)):
                    if k4 != 3:
                        continue
                    for k5, ins in _fields(comp):
                        if k5 == 2:
                            names[bytes(_field(ins, 1)).decode()] = bytes(
                                _field(_field(ins, 7), 2)).decode()
    return out


def _instruction(event_name: str) -> str:
    """The HLO instruction an ``XLA Ops`` event names: the event is
    called ``%while.130 = (s32[], …) while(…)`` or ``while.130``."""
    return event_name.lstrip("%").split(" ", 1)[0]


def attribute_scopes(tr: ScopedTrace,
                     names: dict[str, dict[str, str]]) -> None:
    """Set each operation's scope to its instruction's op_name in the HLO
    of its program run (the ``XLA Modules`` event that contains it)."""
    for op in tr.ops:
        op.scope = names.get(op.program, {}).get(_instruction(op.name),
                                                 op.scope)


# ---- time by scope and by program span --------------------------------------


def _union(iv) -> list[tuple[float, float]]:
    """Merged intervals of ``iv`` (pairs in any order)."""
    merged: list[list[float]] = []
    for a, b in sorted(iv):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _length(iv) -> float:
    return sum(b - a for a, b in iv)


def _overlap(iv, lo: float, hi: float) -> float:
    """Length of the part of intervals ``iv`` that lies in [lo, hi)."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in iv)


def in_scope(op: ScopedOp, scope: str) -> bool:
    """Whether ``op`` ran in the program's device scope ``scope``
    (``fed.<layer>``), as one component of its op_name, under whatever
    transform wraps it (``vmap(fed.local_sgd)/while/body/…``)."""
    return re.search(r"(^|[/(])" + re.escape(scope) + r"($|[/)])",
                     op.scope) is not None


def is_scoped(op: ScopedOp) -> bool:
    """Whether ``op`` ran in any of the program's ``fed.*`` scopes."""
    return re.search(r"(^|[/(])" + re.escape(PROGRAM_PREFIX),
                     op.scope) is not None


def _intervals(tr: ScopedTrace, device: int, pred):
    lo, hi = tr.window()
    return _union(filter(None, (
        trace_reduce._clip(o.start_ns, o.end_ns, lo, hi)
        for o in tr.ops if o.device == device and pred(o))))


def scoped_s(tr: ScopedTrace, device: int, scopes) -> float:
    """Device seconds of ``device`` within the window in which an
    operation of any of ``scopes`` ran: the union of their intervals, so
    that an op nested in another (a loop's body in its ``while``) counts
    once."""
    return _length(_intervals(
        tr, device, lambda o: any(in_scope(o, s) for s in scopes))) / 1e9


def unscoped_s(tr: ScopedTrace, device: int) -> float | None:
    """Busy seconds of ``device`` in which no operation of a ``fed.*``
    scope ran: ops outside every scope, less the time scoped ops cover (an
    unscoped op that encloses scoped ones, such as a loop over rounds,
    adds only its own gaps). None where no op carries a scope."""
    if not any(is_scoped(o) for o in tr.ops if o.device == device):
        return None
    return trace_reduce.busy_s(tr, device) - _length(
        _intervals(tr, device, is_scoped)) / 1e9


def program_spans(tr: ScopedTrace, name: str) -> list[Span]:
    """The program's host spans called ``name`` that overlap the
    window."""
    lo, hi = tr.window()
    return [s for s in tr.program_spans
            if s.name == name and s.end_ns > lo and s.start_ns < hi]


def idle_intervals(tr: ScopedTrace, device: int) -> list[tuple[float, float]]:
    """The idle gaps of ``device`` in the window, in order."""
    lo, hi = tr.window()
    edges = [lo] + [x for iv in trace_reduce.busy_intervals(tr, device)
                    for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def idle_in_spans_s(tr: ScopedTrace, device: int, name: str) -> float:
    """Idle seconds of ``device`` inside the program's host spans called
    ``name``."""
    idle = idle_intervals(tr, device)
    return sum(_overlap(idle, s.start_ns, s.end_ns)
               for s in program_spans(tr, name)) / 1e9


def idle_by_program_span(tr: ScopedTrace,
                         device: int) -> list[tuple[str, float]]:
    """The idle seconds of ``device`` in the window, summed by the
    innermost program span (the latest to start) that the host was in at
    each gap's middle; ``none`` outside every one. Longest first."""
    acc: dict[str, float] = defaultdict(float)
    for a, b in idle_intervals(tr, device):
        mid = (a + b) / 2
        inside = [s for s in tr.program_spans
                  if s.start_ns <= mid < s.end_ns]
        name = max(inside, key=lambda s: s.start_ns).name if inside \
            else "none"
        acc[name] += (b - a) / 1e9
    return sorted(acc.items(), key=lambda kv: -kv[1])


def split(tr: ScopedTrace, device: int, rounds: int = 0) -> dict:
    """The device time of ``device`` in the window by layer, in ms:
    local SGD, the server's scopes, evaluation, and busy time no scope
    covers, which add up to ``busy_ms``; per evaluation (``fed.eval``
    host spans) its device time and the idle inside it; idle by program
    span; per round where ``rounds`` is given. None for what the trace
    holds nothing of."""
    busy = trace_reduce.busy_s(tr, device)
    un = unscoped_s(tr, device)
    evals = len(program_spans(tr, "fed.eval"))
    out = {"window_ms": 1e3 * trace_reduce.window_s(tr),
           "busy_ms": 1e3 * busy,
           "local_sgd_ms": 1e3 * scoped_s(tr, device, LOCAL_SGD),
           "server_ms": 1e3 * scoped_s(tr, device, SERVER),
           "eval_ms": 1e3 * scoped_s(tr, device, EVAL),
           "unscoped_ms": None if un is None else 1e3 * un,
           "unscoped_device_share": (None if un is None or busy <= 0
                                     else 100.0 * un / busy),
           "evals": evals,
           "eval_ms_per_eval": None, "eval_idle_ms_per_eval": None,
           "program_idle_ms": {n: 1e3 * s for n, s in
                               idle_by_program_span(tr, device)}}
    if evals:
        out["eval_ms_per_eval"] = out["eval_ms"] / evals
        out["eval_idle_ms_per_eval"] = 1e3 * idle_in_spans_s(
            tr, device, "fed.eval") / evals
    if rounds:
        out["local_sgd_ms_per_round"] = out["local_sgd_ms"] / rounds
        out["server_ms_per_round"] = out["server_ms"] / rounds
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("trace_dir", help="a jax.profiler trace directory")
    ap.add_argument("--rounds", type=int, default=0,
                    help="rounds the trace holds, for per-round times")
    a = ap.parse_args(argv)
    tr = read_xplane(trace_reduce.find_xplane(a.trace_dir))
    if not tr.devices:
        print("the trace holds no device operations", file=sys.stderr)
        return 1
    print(json.dumps(split(tr, tr.devices[0], a.rounds)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
