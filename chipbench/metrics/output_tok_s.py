"""Output tokens whose stream burst was stamped inside the window, per
second of the window."""


def read(run):
    n = sum(k for _, k, t in run.bursts if t < run.seconds)
    return n / run.seconds
