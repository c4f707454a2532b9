"""90th percentile, over every request due in the window, of its admission
stamp minus its due time (infinite for one never admitted)."""

import math

from chipbench.stats import nearest_rank


def read(run):
    return nearest_rank(
        [1e3 * (r.admitted_s - r.due_s) if r.admitted_s is not None
         else math.inf for r in run.due_in_window()], 90)
