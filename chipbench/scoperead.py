"""Device seconds of the operations a program names with ``jax.named_scope``,
and the traced window's decode macro-steps as the program's spans saw
them, for the per-layer metrics of one part of a step."""

from __future__ import annotations

from typing import Iterable, List

MACRO = "jit(macro_fn)"


def macro_scope_s(run, scopes: Iterable[str]) -> float:
    """Device seconds, in the traced window, of the operations of the
    decode macro-step program whose scope path has one of ``scopes`` as a
    part (0 where the trace or the scopes are missing)."""
    if run.trace is None:
        return 0.0
    want = set(scopes)
    return sum(v for k, v in run.trace.scope_s.items()
               if k.split("/")[0] == MACRO and want & set(k.split("/")))


def traced_macro_spans(run) -> List:
    """The ``serve/macro`` spans that dispatched a step, inside the traced
    window (none where the run has no spans or no trace)."""
    end = run.trace_end_s
    if getattr(run, "spans", None) is None or end is None:
        return []
    return [s for s in run.spans if s.name == "serve/macro"
            and "k" in s.attrs and 0 <= s.start and s.end <= end]
