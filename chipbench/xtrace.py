"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time, per-program device time, each
operation's device time by its name and by the scope path it carries, the
costliest operations and the longest idle gaps.

Device planes are those named ``/device:...``; on a TPU each holds an
``XLA Modules`` line (one event per program execution, named after the
jitted function: ``jit_prefill_fn``, ``jit_macro_fn``) and an ``XLA Ops``
line (one event per operation).  The harness marks the window with two host
annotations, ``chipbench.window_open`` and ``chipbench.window_close``;
device events are clipped to the interval between them.  A device is busy
while an operation runs or an asynchronous copy (``Async XLA Ops``: the
scan's weight slices streaming in) is in flight.

An operation's scope path is the ``op_name`` metadata of its HLO
instruction (``jit(macro_fn)/while/body/<named_scope>/dot_general``).  The
device events do not carry it; the profiler keeps each program's HLO proto
on the ``/host:metadata`` plane, as the ``Hlo Proto`` stat of an event
metadata named like the program's ``XLA Modules`` events
(``jit_macro_fn(12)``).  ``op_scopes`` reads them from the file's bytes,
which ``ProfileData`` does not expose.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

OPEN, CLOSE = "chipbench.window_open", "chipbench.window_close"
MODULES, OPS, ASYNC = "XLA Modules", "XLA Ops", "Async XLA Ops"
METADATA_PLANE, HLO_PROTO = "/host:metadata", "Hlo Proto"
# a program's kind, from its module name
KINDS = (("macro_fn", "macro"), ("prefill_fn", "prefill"))

Interval = Tuple[float, float]  # (start_ns, end_ns)


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Device:
    name: str
    modules: List[Event]
    ops: List[Event]
    copies: List[Event] = dataclasses.field(default_factory=list)  # async


def short(name: str) -> str:
    """An HLO op's event name cut to its instruction and result type:
    ``%fusion.1 = bf16[2,8192]{...} fusion(...)`` -> ``fusion.1 =
    bf16[2,8192]``; other names as they are."""
    if not name.startswith("%"):
        return name
    return name[1:].split("{", 1)[0].split(" fusion(", 1)[0].strip()


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # averaged over devices
    module_s: Dict[str, float]  # kind -> device seconds (all devices)
    module_count: Dict[str, int]
    top_ops: List[Tuple[str, float]]  # the largest of op_s
    idle_gaps: List[Tuple[str, float]]
    # device seconds of the operations (all devices, loops not counted):
    # by "<program kind>/<op name>", and by the scope path an op carries
    # (``jit(macro_fn)/.../<named_scope>/...``) where it carries one
    op_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    scope_s: Dict[str, float] = dataclasses.field(default_factory=dict)

    def under(self, scope: str) -> float:
        """Device seconds of the operations whose scope path has ``scope``
        (a ``jax.named_scope`` name) as one of its parts."""
        return sum(v for k, v in self.scope_s.items()
                   if scope in k.split("/"))


def module_kind(name: str) -> str:
    for key, kind in KINDS:
        if key in name:
            return kind
    return "other"


def _varint(b, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """(field number, value) of the protobuf message ``b`` (a memoryview):
    an int for a varint, a memoryview for a length-delimited field, bytes
    for a fixed-width one."""
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = bytes(b[i:i + n]), i + n
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _hlo_op_names(hlo_proto) -> Dict[str, str]:
    """Instruction name -> ``op_name`` of every instruction of an HloProto
    (``hlo_module`` 1 > ``computations`` 3 > ``instructions`` 2 > ``name``
    1, ``metadata`` 7 > ``op_name`` 2) that has one."""
    out = {}
    for f, module in _fields(hlo_proto):
        for f2, comp in _fields(module) if f == 1 else ():
            for f3, ins in _fields(comp) if f2 == 3 else ():
                if f3 != 2:
                    continue
                name = op_name = None
                for f4, v in _fields(ins):
                    if f4 == 1:
                        name = bytes(v).decode()
                    elif f4 == 7:
                        op_name = next((bytes(x).decode() for f5, x in
                                        _fields(v) if f5 == 2), None)
                if name and op_name:
                    out[name] = op_name
    return out


def op_scopes(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """Program (as its ``XLA Modules`` events are named) -> HLO instruction
    name -> scope path, from the serialized XSpace of a trace (``planes`` 1
    > ``name`` 2, ``event_metadata`` 4, ``stat_metadata`` 5; an XStat's
    ``metadata_id`` 1 and ``bytes_value`` 6)."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        name, events, stat_names = None, [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
                if name != METADATA_PLANE:
                    break
            elif pf in (4, 5):  # map entries: key 1, value 2
                value = next((x for ef, x in _fields(v) if ef == 2), b"")
                if pf == 4:
                    events.append(value)
                else:
                    meta = dict(_fields(value))
                    stat_names[meta.get(1)] = bytes(meta.get(2, b"")).decode()
        if name != METADATA_PLANE:
            continue
        for ev in events:
            program, protos = None, []
            for ef, v in _fields(ev):
                if ef == 2:
                    program = bytes(v).decode()
                elif ef == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == HLO_PROTO and 6 in stat:
                        protos.append(stat[6])
            for proto in protos:
                out.setdefault(program, {}).update(_hlo_op_names(proto))
    return out


def from_profile(pd) -> Tuple[List[Device], Dict[str, float]]:
    devices, marks = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            def ev(ln):
                return [Event(short(e.name), e.start_ns, e.end_ns)
                        for e in ln.events] if ln is not None else []
            dev = Device(plane.name, ev(lines.get(MODULES)),
                         ev(lines.get(OPS)), ev(lines.get(ASYNC)))
            if dev.modules or dev.ops:  # planes of chips this run used
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in (OPEN, CLOSE) and e.name not in marks:
                        marks[e.name] = e.start_ns
    return devices, marks


def union_ns(intervals: Sequence[Interval]) -> List[Interval]:
    """The union of ``intervals`` as sorted disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [Event(e.name, max(e.start_ns, lo), min(e.end_ns, hi))
            for e in events if e.end_ns > lo and e.start_ns < hi]


def leaves(ops: Sequence[Event]) -> List[Event]:
    """The operations that contain no other: a loop (``while``) or a call
    spans the operations of its body on the same line, and would count
    their time twice."""
    ops = sorted(ops, key=lambda e: (e.start_ns, -e.end_ns))
    return [e for e, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt.start_ns >= e.end_ns]


def summarize(devices: List[Device], marks: Dict[str, float], *,
              label: Optional[Callable[[float, float], str]] = None,
              scopes: Optional[Dict[str, Dict[str, str]]] = None,
              top: int = 10) -> Summary:
    """Busy and idle time of the window between the harness's marks (the
    whole trace where a mark is missing).  ``label(start_s, end_s)`` names
    what the host was doing in an idle gap, in seconds after the window
    opened; ``scopes`` is ``op_scopes`` of the trace."""
    every = [e for d in devices for e in (d.ops or d.modules)]
    if not every:
        raise ValueError("no device events in the trace")
    lo = marks.get(OPEN, min(e.start_ns for e in every))
    hi = marks.get(CLOSE, max(e.end_ns for e in every))
    busy, module_s, module_n, op_s, scope_s = 0.0, {}, {}, {}, {}
    gaps: List[Interval] = []
    for d in devices:
        mods = sorted(clip(d.modules, lo, hi), key=lambda e: e.start_ns)
        for m in mods:
            k = module_kind(m.name)
            module_s[k] = module_s.get(k, 0.0) + (m.end_ns - m.start_ns) / 1e9
            module_n[k] = module_n.get(k, 0) + 1
        # busy: an operation runs, or an asynchronous copy is in flight
        ops = clip((d.ops or d.modules) + d.copies, lo, hi)
        spans = union_ns([(e.start_ns, e.end_ns) for e in ops])
        busy += sum(b - a for a, b in spans) / 1e9
        edges = [lo] + [x for s in spans for x in s] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        starts = [m.start_ns for m in mods]
        for e in leaves(clip(d.ops, lo, hi)):
            i = bisect.bisect_right(starts, e.start_ns) - 1
            mod = (mods[i].name if i >= 0 and e.start_ns < mods[i].end_ns
                   else None)
            kind = module_kind(mod) if mod is not None else "other"
            key, dur = f"{kind}/{e.name}", (e.end_ns - e.start_ns) / 1e9
            op_s[key] = op_s.get(key, 0.0) + dur
            scope = (scopes or {}).get(mod, {}).get(e.name.split(" = ")[0])
            if scope:
                scope_s[scope] = scope_s.get(scope, 0.0) + dur
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return Summary(
        window_s=(hi - lo) / 1e9, busy_s=busy / len(devices),
        module_s=module_s, module_count=module_n,
        top_ops=sorted(op_s.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=[((label((a - lo) / 1e9, (b - lo) / 1e9) if label
                     else "idle"), (b - a) / 1e9) for a, b in longest],
        op_s=op_s, scope_s=scope_s)
