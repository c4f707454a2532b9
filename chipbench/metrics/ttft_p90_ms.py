"""90th percentile, over every request due in the window, of its first
token's stamp minus its due time; a request that failed counts as
infinite."""

import math

from chipbench.stats import nearest_rank


def read(run):
    return nearest_rank(
        [1e3 * (r.first_s - r.due_s) if r.done else math.inf
         for r in run.due_in_window()], 90)
