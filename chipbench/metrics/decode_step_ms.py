"""Device time of the decode macro-step program (``jit(macro_fn)``) in the
traced window, divided by the decode steps those macro-steps ran."""


def read(run):
    steps = sum(s.k for s in run.traced_steps())
    if run.trace is None or not steps or "macro" not in run.trace.module_s:
        return None
    return 1e3 * run.trace.module_s["macro"] / steps
