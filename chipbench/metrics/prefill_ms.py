"""Mean over the admission groups of the window of first-token stamp minus
admission stamp: one padded group prefill, ending in its host sync."""


def read(run):
    g = [a.t_first - a.t_admit for a in run.admissions
         if a.t_admit < run.seconds]
    return 1e3 * sum(g) / len(g) if g else None
