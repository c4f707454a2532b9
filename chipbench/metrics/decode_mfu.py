"""Useful FLOPs of the traced window's decode steps (as the configuration's
family counts them) over the device time of the macro-step program times
the chip's peak, in percent."""


def read(run):
    if run.trace is None or "macro" not in run.trace.module_s:
        return None
    fam = run.cell.family
    flops = sum(fam.token_flops(run.sizes, c) for s in run.traced_steps()
                for j in range(s.k) for c in s.contexts(j))
    return 100 * flops / (run.trace.module_s["macro"]
                          * run.peak["bf16_flops_per_s"]) if flops else None
