"""Useful FLOPs of the prompts prefilled and the tokens decoded inside the
window over the window times the chip's peak, in percent."""

from chipbench import costs


def read(run):
    s, end = run.sizes, run.seconds
    flops = sum(costs.prefill_flops(s, p) for a in run.admissions
                if a.t_first < end for p in a.prompt_lens)
    flops += sum(costs.token_flops(s, c) for st in run.steps if st.t < end
                 for j in range(st.k) for c in st.contexts(j))
    return 100 * flops / (end * run.peak["bf16_flops_per_s"])
