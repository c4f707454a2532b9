"""Host spans of the serve path, kept in memory on the engine's clock.

A ``SpanRecorder`` passed to ``Runtime.serve(tracer=...)`` or
``ContinuousServeEngine(tracer=...)`` records one ``Span`` per layer
boundary the engine crosses (``serve/setup``, ``serve/intake``,
``serve/admit`` with its phases, ``serve/macro`` with its phases,
``serve/wait_arrival``; DESIGN.md §5).  Times are ``time.perf_counter()``,
the clock the engine's default ``now_fn`` reads, so a caller that passes its
own perf-counter clock can subtract its origin and lay spans beside request
stamps.  Each span is also entered as a ``jax.profiler.TraceAnnotation``
under its plain name, so a running ``jax.profiler.trace`` puts it on the host
plane, on the same clock as the device planes.

While the recorder is open, every backend compile JAX reports adds its
seconds to the ``compile_s`` attribute of the innermost span open in the
compiling thread: a span that shows ``compile_s`` recompiled.

With no recorder (``tracer=None``, the default) the engine allocates no span
and enters no annotation.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass(eq=False)
class Span:
    """One interval of host work.  ``parent`` is the id of the span that
    encloses it (None at the top); ``end`` is None while it is open."""

    id: int
    parent: Optional[int]
    name: str
    start: float
    end: Optional[float] = None
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)


class SpanRecorder:
    """Records spans in memory.  Each thread keeps its own stack of open
    spans, so a span opened in a worker thread (the watchdog's guarded
    dispatch) names its parent explicitly.  ``close()`` (or leaving a
    ``with`` block) stops the compile attribution; recorded spans stay."""

    def __init__(self):
        self.spans: List[Span] = []  # closed spans, in the order they closed
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._listening = True
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def __enter__(self) -> "SpanRecorder":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        if self._listening:
            self._listening = False
            jax.monitoring.unregister_event_duration_listener(self._on_event)

    def _stack(self) -> List[Tuple[Span, Any]]:
        """This thread's open spans, each with its entered annotation."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def open(self, name: str, parent: Optional[Span] = None) -> Span:
        """Start a span under ``parent`` (default: the innermost span open
        in this thread) and make it this thread's innermost."""
        stack = self._stack()
        if parent is None and stack:
            parent = stack[-1][0]
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        span = Span(next(self._ids), parent.id if parent else None, name,
                    time.perf_counter())
        stack.append((span, ann))
        return span

    def end(self, span: Span) -> None:
        """End ``span`` if it is open in this thread.  Spans opened above
        it and left open (an exception unwound past an explicit ``open``)
        end with it."""
        t = time.perf_counter()
        stack = self._stack()
        while any(s is span for s, _ in stack):
            top, ann = stack.pop()
            ann.__exit__(None, None, None)
            top.end = t
            self.spans.append(top)

    @contextlib.contextmanager
    def span(self, name: str,
             parent: Optional[Span] = None) -> Iterator[Span]:
        s = self.open(name, parent)
        try:
            yield s
        finally:
            self.end(s)

    def _on_event(self, event: str, duration: float, **_) -> None:
        stack = self._stack()
        if event == COMPILE_EVENT and stack:
            attrs = stack[-1][0].attrs
            attrs["compile_s"] = attrs.get("compile_s", 0.0) + duration


_OFF = contextlib.nullcontext()


def maybe_span(tracer: Optional[SpanRecorder], name: str,
               parent: Optional[Span] = None):
    """``tracer.span(name, parent)``, or with no recorder a shared no-op
    context that yields None (no allocation, no annotation)."""
    return _OFF if tracer is None else tracer.span(name, parent)
