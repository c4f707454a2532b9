"""The program's host spans, read for the per-layer span metrics.

A run served with a ``repro.serving.spans.SpanRecorder`` passed to
``Runtime.serve(tracer=...)`` has its spans as ``run.spans``, on the window
clock (``on_window``: seconds after the window opened, so set-up spans are
negative).  A run without them (``run.spans`` absent or None, as from a
program that has no recorder) gives every reader here None.

The same spans, entered as profiler annotations, lie on the host plane of a
``--trace 1`` run's ``.xplane.pb`` under their names (``serve/...``), on the
device planes' clock: ``host_spans`` reads them, ``idle_by_span`` splits the
window's device-idle time by the innermost span that covers it.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import xtrace

PREFIX = "serve/"


def on_window(spans, t0: float) -> list:
    """The recorder's spans with ``t0`` (the window's opening, on the same
    perf-counter clock) subtracted from their start and end."""
    return [dataclasses.replace(s, start=s.start - t0, end=s.end - t0)
            for s in spans]


def window_spans(run, name: str) -> Optional[list]:
    """Spans called ``name`` that start inside the window, or None where
    the run has no spans."""
    spans = getattr(run, "spans", None)
    if spans is None:
        return None
    return [s for s in spans if s.name == name and 0 <= s.start < run.seconds]


def covered(intervals: Sequence[Tuple[float, float]], lo: float,
            hi: float) -> float:
    """Length of ``[lo, hi)`` that the union of ``intervals`` covers."""
    clipped = [(max(a, lo), min(b, hi)) for a, b in intervals
               if b > lo and a < hi]
    return sum(b - a for a, b in xtrace.union_ns(clipped))


def wait_behind(run, name: str) -> Optional[float]:
    """Mean over the requests due in the window and admitted of the part of
    their queue wait (due to admitted) that spans ``name`` of other
    requests cover, in ms.  A span that took up no request (an admission
    round that admitted none) covers nobody's wait."""
    spans = getattr(run, "spans", None)
    if spans is None:
        return None
    steps = [s for s in spans if s.name == name and s.attrs.get("rids")]
    waits = [covered([(s.start, s.end) for s in steps
                      if r.rid not in s.attrs["rids"]],
                     r.due_s, r.admitted_s)
             for r in run.due_in_window() if r.admitted_s is not None]
    return 1e3 * sum(waits) / len(waits) if waits else None


def attr_share(run, name: str, num: str, rows: str,
               length: str) -> Optional[float]:
    """Sum of attribute ``num`` over sum of ``rows`` x ``length`` over the
    window's spans ``name``, in percent."""
    spans = [s.attrs for s in window_spans(run, name) or ()
             if num in s.attrs]
    den = sum(a[rows] * a[length] for a in spans)
    return 100 * sum(a[num] for a in spans) / den if den else None


# ------------------------------------------------- the profiler's host plane --


def host_spans(pd) -> List[xtrace.Event]:
    """The ``serve/`` annotations on the host planes of a ``ProfileData``,
    in nanoseconds on the trace's clock."""
    return [xtrace.Event(e.name, e.start_ns, e.end_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIX)]


def innermost(events: Sequence[xtrace.Event]) -> List[xtrace.Event]:
    """Disjoint intervals, each named after the innermost (latest-started)
    span open over it."""
    points = sorted([(e.start_ns, 1, i) for i, e in enumerate(events)]
                    + [(e.end_ns, 0, i) for i, e in enumerate(events)])
    out: List[xtrace.Event] = []
    open_: List[int] = []
    prev = None
    for t, starts, i in points:
        if open_ and t > prev:
            top = max(open_, key=lambda j: (events[j].start_ns,
                                            -events[j].end_ns))
            out.append(xtrace.Event(events[top].name, prev, t))
        if starts:
            open_.append(i)
        else:
            open_.remove(i)
        prev = t
    return out


def idle_gaps(devices: List[xtrace.Device],
              marks: Dict[str, float]) -> List[List[Tuple[float, float]]]:
    """Per device, the idle intervals of the window between the harness's
    marks, as ``xtrace.summarize`` finds them."""
    every = [e for d in devices for e in (d.ops or d.modules)]
    lo = marks.get(xtrace.OPEN, min(e.start_ns for e in every))
    hi = marks.get(xtrace.CLOSE, max(e.end_ns for e in every))
    out = []
    for d in devices:
        ops = xtrace.clip((d.ops or d.modules) + d.copies, lo, hi)
        busy = xtrace.union_ns([(e.start_ns, e.end_ns) for e in ops])
        edges = [lo] + [x for s in busy for x in s] + [hi]
        out.append([(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]])
    return out


def idle_by_span(devices: List[xtrace.Device], marks: Dict[str, float],
                 host: Sequence[xtrace.Event]) -> Optional[Dict[str, float]]:
    """Device-idle seconds of the window by the innermost ``serve/`` span
    covering them (``none`` where no span does), averaged over devices;
    None where the trace holds no device events."""
    if not any(d.ops or d.modules for d in devices):
        return None
    segs = innermost(host)
    out: Dict[str, float] = {}
    for gaps in idle_gaps(devices, marks):
        i = 0
        for a, b in gaps:
            idle = b - a
            while i < len(segs) and segs[i].end_ns <= a:
                i += 1
            j = i
            while j < len(segs) and segs[j].start_ns < b:
                s = segs[j]
                part = min(b, s.end_ns) - max(a, s.start_ns)
                out[s.name] = out.get(s.name, 0.0) + part / 1e9
                idle -= part
                j += 1
            if idle > 0:
                out["none"] = out.get("none", 0.0) + idle / 1e9
    return {k: v / len(devices) for k, v in
            sorted(out.items(), key=lambda kv: -kv[1])}


def leaf_share(idle: Dict[str, float],
               host: Sequence[xtrace.Event]) -> Optional[float]:
    """Share of the idle seconds whose innermost span is a leaf: a span
    whose name no other span of ``host`` extends."""
    total = sum(idle.values())
    names = {e.name for e in host}
    leaf = sum(v for n, v in idle.items() if n in names
               and not any(m.startswith(n + "/") for m in names))
    return 100 * leaf / total if total else None


def lines(run, idle: Optional[Dict[str, float]],
          host: Sequence[xtrace.Event] = ()) -> List[str]:
    """Two lines for standard error: the idle split (from the ``host``
    spans), and the traced window's ``serve/macro`` spans beside the
    trace's macro-step programs and the macro-steps the stream saw."""
    out = []
    if idle is not None:
        out.append(
            "device idle s by innermost serve/ span: "
            + str({k: round(v, 6) for k, v in idle.items()})
            + f"; in a leaf span {leaf_share(idle, host):.1f}%")
    spans = getattr(run, "spans", None)
    end = run.trace_end_s
    if spans is not None and end is not None:
        macros = [s for s in spans if s.name == "serve/macro"
                  and s.end > 0 and s.start < end]
        steps = run.traced_steps()
        programs = (run.trace.module_count.get("macro")
                    if run.trace is not None else None)
        out.append(
            f"serve/macro spans in the traced window: {len(macros)}, sum K "
            f"{sum(s.attrs['k'] for s in macros)}; macro-step programs in "
            f"the trace: {programs}; the stream saw {len(steps)}, sum K "
            f"{sum(s.k for s in steps)}")
    return out
