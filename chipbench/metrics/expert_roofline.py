"""Least time of the expert layers in the traced window's decode
macro-steps over the device seconds of the operations under the program's
``moe_router``, ``moe_experts`` and ``moe_shared`` scopes in
``jit(macro_fn)``, in percent.  A macro-step's least time is the larger of
its bytes over the memory bandwidth (the held experts its live tokens
reached, the ``experts_hit`` of its ``serve/macro`` span, and in every
step of every expert layer the shared experts and the router) and its
FLOPs over the peak (the routed pairs on held experts, ``held_assignments``,
and each emitted token's shared experts and router), as the
configuration's family counts them.  None where the program names no such
scope or its spans carry no counts."""

from chipbench import scoperead

SCOPES = ("moe_router", "moe_experts", "moe_shared")


def read(run):
    fam, s, pk = run.cell.family, run.sizes, run.peak
    device_s = scoperead.macro_scope_s(run, SCOPES)
    spans = [sp for sp in scoperead.traced_macro_spans(run)
             if "experts_hit" in sp.attrs]
    if not device_s or not spans or not hasattr(fam, "expert_bytes"):
        return None
    layers = s["layers"] - s["dense_layers"]
    expert_flops = 2 * fam.expert_params(s)
    fixed_flops = 2 * fam.moe_fixed_params(s)
    least = 0.0
    for sp in spans:
        a = sp.attrs
        nbytes = fam.expert_bytes(s, a["experts_hit"], a["k"] * layers)
        flops = (a["held_assignments"] * expert_flops
                 + a["emitted"] * layers * fixed_flops)
        least += max(nbytes / pk["hbm_bytes_per_s"],
                     flops / pk["bf16_flops_per_s"])
    return 100 * least / device_s
