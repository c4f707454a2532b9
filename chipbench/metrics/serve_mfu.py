"""Useful FLOPs of the prompts prefilled and the tokens decoded inside the
window (as the configuration's family counts them) over the window times the
chip's peak, in percent."""


def read(run):
    fam, s, end = run.cell.family, run.sizes, run.seconds
    flops = sum(fam.prefill_flops(s, p) for a in run.admissions
                if a.t_first < end for p in a.prompt_lens)
    flops += sum(fam.token_flops(s, c) for st in run.steps if st.t < end
                 for j in range(st.k) for c in st.contexts(j))
    return 100 * flops / (end * run.peak["bf16_flops_per_s"])
