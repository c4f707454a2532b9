"""Least time of the traced window's decode steps over their device time,
in percent.  A step's least time is the larger of its bytes (every weight
once, plus the live cache of the sequences it advances) over the memory
bandwidth and its useful FLOPs over the peak, as the configuration's family
counts them; cache past a sequence's length and masked rows are not
counted."""

from chipbench import costs


def read(run):
    if run.trace is None or "macro" not in run.trace.module_s:
        return None
    fam = run.cell.family
    least = sum(costs.decode_step_least_s(fam, run.sizes, s.contexts(j),
                                          run.peak)
                for s in run.traced_steps() for j in range(s.k)
                if s.contexts(j))
    return 100 * least / run.trace.module_s["macro"] if least else None
