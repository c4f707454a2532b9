"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time, per-program device time, the
costliest device operations and the longest idle gaps.

Device planes are those named ``/device:...``; on a TPU each holds an
``XLA Modules`` line (one event per program execution, named after the
jitted function: ``jit_prefill_fn``, ``jit_macro_fn``) and an ``XLA Ops``
line (one event per operation).  The harness marks the window with two host
annotations, ``chipbench.window_open`` and ``chipbench.window_close``;
device events are clipped to the interval between them.  A device is busy
while an operation runs or an asynchronous copy (``Async XLA Ops``: the
scan's weight slices streaming in) is in flight.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

OPEN, CLOSE = "chipbench.window_open", "chipbench.window_close"
MODULES, OPS, ASYNC = "XLA Modules", "XLA Ops", "Async XLA Ops"
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
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def module_kind(name: str) -> str:
    for key, kind in KINDS:
        if key in name:
            return kind
    return "other"


def load(path: str) -> Tuple[List[Device], Dict[str, float]]:
    """Device planes and the first start of each host annotation."""
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


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
              top: int = 10) -> Summary:
    """Busy and idle time of the window between the harness's marks (the
    whole trace where a mark is missing).  ``label(start_s, end_s)`` names
    what the host was doing in an idle gap, in seconds after the window
    opened."""
    every = [e for d in devices for e in (d.ops or d.modules)]
    if not every:
        raise ValueError("no device events in the trace")
    lo = marks.get(OPEN, min(e.start_ns for e in every))
    hi = marks.get(CLOSE, max(e.end_ns for e in every))
    busy, module_s, module_n, op_s = 0.0, {}, {}, {}
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
            kind = (module_kind(mods[i].name)
                    if i >= 0 and e.start_ns < mods[i].end_ns else "other")
            key = f"{kind}/{e.name}"
            op_s[key] = op_s.get(key, 0.0) + (e.end_ns - e.start_ns) / 1e9
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return Summary(
        window_s=(hi - lo) / 1e9, busy_s=busy / len(devices),
        module_s=module_s, module_count=module_n,
        top_ops=sorted(op_s.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=[((label((a - lo) / 1e9, (b - lo) / 1e9) if label
                     else "idle"), (b - a) / 1e9) for a, b in longest])
