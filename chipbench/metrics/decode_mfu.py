"""Useful FLOPs of the traced window's decode steps over the device time of
the macro-step program times the chip's peak, in percent."""

from chipbench import costs


def read(run):
    if run.trace is None or "macro" not in run.trace.module_s:
        return None
    flops = sum(costs.token_flops(run.sizes, c) for s in run.traced_steps()
                for j in range(s.k) for c in s.contexts(j))
    return 100 * flops / (run.trace.module_s["macro"]
                          * run.peak["bf16_flops_per_s"]) if flops else None
