"""Least time of the latent attention in the traced window's decode steps
over the device seconds of the operations under the program's ``mla``
scope in ``jit(macro_fn)``, in percent.  A step's least time is the larger
of its bytes (every layer's attention weights once, plus each live
sequence's latent cache) over the memory bandwidth and its FLOPs (the
absorbed form) over the peak, as the configuration's family counts them.
None where the program names no ``mla`` scope or the family has no latent
attention."""

from chipbench import scoperead


def read(run):
    fam, s, pk = run.cell.family, run.sizes, run.peak
    device_s = scoperead.macro_scope_s(run, ["mla"])
    if not device_s or not hasattr(fam, "mla_flops"):
        return None
    least = 0.0
    for st in run.traced_steps():
        for j in range(st.k):
            ctx = st.contexts(j)
            if not ctx:
                continue
            nbytes = (fam.mla_weight_bytes(s)
                      + sum(ctx) * fam.latent_bytes_per_token(s))
            flops = s["layers"] * sum(fam.mla_flops(s, c) for c in ctx)
            least += max(nbytes / pk["hbm_bytes_per_s"],
                         flops / pk["bf16_flops_per_s"])
    return 100 * least / device_s if least else None
