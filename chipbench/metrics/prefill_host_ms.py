"""Mean over the window's ``serve/admit`` spans that dispatched a prefill
of their duration minus their ``serve/admit/sync`` children: the host's
part of an admission (decision, radix lookups, input preparation and
upload, dispatch, first-token parse)."""

from chipbench import spanread


def read(run):
    admits = [s for s in spanread.window_spans(run, "serve/admit") or ()
              if "rids" in s.attrs]
    if not admits:
        return None
    sync = {}
    for s in run.spans:
        if s.name == "serve/admit/sync":
            sync[s.parent] = sync.get(s.parent, 0.0) + s.end - s.start
    return 1e3 * sum(a.end - a.start - sync.get(a.id, 0.0)
                     for a in admits) / len(admits)
