"""Routed (token, expert) pairs that landed on held experts in the
window's prefills (``serve/admit`` attribute ``held_assignments``) over
the pairs those prefills computed: rows x padded length x held experts x
expert layers (the dense expert product computes every held expert for
every row), in percent.  None where the spans carry no counts."""

from chipbench import spanread


def read(run):
    s = run.sizes
    if "held" not in s:
        return None
    share = spanread.attr_share(run, "serve/admit", "held_assignments",
                                "rows", "padded_len")
    if share is None:
        return None
    return share / (s["held"] * (s["layers"] - s["dense_layers"]))
