"""The dense decoder family (Llama, Mistral, Phi-3): every part of the
benchmark that knows a configuration's shape.

A family module lives in ``families/<name>.py`` and is found by the
configuration file's ``reference`` key, as the plain reference
``references/<name>.py`` of the same name is.  It gives:

* ``sizes(config)``: the plain sizes the reference, the weights and the
  work counts read;
* ``program_config(config)``: the program's ModelConfig for the file;
* ``leaf_shapes(sizes)``: every weight ``weights.draw`` makes;
* ``program_params(w, model)``: the drawn weights in the program's
  parameter layout (``weights.program_params`` checks it leaf by leaf);
* the work counts the cost readers read: ``param_count``,
  ``weight_bytes``, ``decode_bytes``, ``token_flops``, ``prefill_flops``.

Only useful work counts: rows a program pads or masks, logits it computes
for prompt positions nobody reads, and cache it reads past a sequence's
length are not in these numbers, so a change that removes them raises a
share and cannot push it past 100%.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Sequence

from chipbench.costs import dtype_bytes
from chipbench.spec import SpecError

# published config key -> field of the program's ModelConfig
PROGRAM_FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "max_position_embeddings": "max_seq_len",
    "dtype": "dtype",
}
# the published activation of the gated MLP -> the program's name for it
PROGRAM_ACTIVATION = {"silu": "swiglu"}


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The plain sizes, with the head size resolved the published way."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    return {
        "layers": config["num_hidden_layers"], "d": d, "heads": h,
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim") or d // h,
        "ff": config["intermediate_size"], "vocab": config["vocab_size"],
        "rope_theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "tied": bool(config.get("tie_word_embeddings", False)),
        "dtype": config.get("dtype", "bfloat16"),
    }


def program_config(config: Dict[str, Any]):
    """The registered architecture ``program_arch`` with every published
    size of the file written over it, so what runs is what the file
    states."""
    from repro.configs import get_config

    base = get_config(config["program_arch"])
    fields = {PROGRAM_FIELDS[k]: config[k] for k in PROGRAM_FIELDS
              if k in config}
    act = config.get("hidden_act")
    if act is not None:
        if act not in PROGRAM_ACTIVATION:
            raise SpecError(f"no program activation for {act!r}")
        fields["activation"] = PROGRAM_ACTIVATION[act]
    return dataclasses.replace(base, **fields)


def leaf_shapes(s: Dict) -> Dict[str, tuple]:
    """name -> (shape, scale): ``scale`` is the fan-in of a matrix, None for
    the 0.02 tables, ``"norm"`` for a gain offset."""
    L, d, h, kv, hd, f, v = (s["layers"], s["d"], s["heads"], s["kv_heads"],
                             s["head_dim"], s["ff"], s["vocab"])
    return {
        "embed": ((v, d), None),
        "lm_head": ((v, d), None),
        "final_norm": ((d,), "norm"),
        "attn_norm": ((L, d), "norm"),
        "wq": ((L, d, h, hd), d),
        "wk": ((L, d, kv, hd), d),
        "wv": ((L, d, kv, hd), d),
        "wo": ((L, h * hd, d), h * hd),
        "mlp_norm": ((L, d), "norm"),
        "w_gate": ((L, d, f), d),
        "w_up": ((L, d, f), d),
        "w_down": ((L, f, d), f),
    }


def program_params(w: Dict, model) -> Dict:
    """The drawn weights in the program's parameter layout."""
    return {
        "embed": w["embed"], "unembed": w["lm_head"],
        "final_norm": {"scale": w["final_norm"]},
        "layers": {
            "ln1": {"scale": w["attn_norm"]},
            "ln2": {"scale": w["mlp_norm"]},
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "ffn": {"w_in": w["w_up"], "w_gate": w["w_gate"],
                    "w_out": w["w_down"]},
        },
    }


# ------------------------------------------------------------ work counts --


def layer_matmul_params(s: Dict) -> int:
    """Parameters of one layer's matrices: q, k, v, o and the gated MLP."""
    d, h, kv, hd, f = s["d"], s["heads"], s["kv_heads"], s["head_dim"], s["ff"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def param_count(s: Dict) -> int:
    """Every parameter: layers, norms, embedding and (untied) output head."""
    norms = (2 * s["layers"] + 1) * s["d"]
    tables = s["vocab"] * s["d"] * (1 if s["tied"] else 2)
    return s["layers"] * layer_matmul_params(s) + norms + tables


def weight_bytes(s: Dict) -> int:
    """Bytes of every weight as served (norm gains in float32)."""
    norms = (2 * s["layers"] + 1) * s["d"]
    return (param_count(s) - norms) * dtype_bytes(s["dtype"]) + 4 * norms


def decode_weight_bytes(s: Dict, rows: int) -> int:
    """Weight bytes one decode step must read: every layer matrix, the norms
    and the output head once, and one embedding row per sequence."""
    b = dtype_bytes(s["dtype"])
    norms = (2 * s["layers"] + 1) * s["d"]
    return (s["layers"] * layer_matmul_params(s) * b + 4 * norms
            + s["vocab"] * s["d"] * b + rows * s["d"] * b)


def kv_bytes_per_token(s: Dict) -> int:
    """Key and value bytes one token keeps in the cache, over all layers."""
    return s["layers"] * 2 * s["kv_heads"] * s["head_dim"] * dtype_bytes(
        s["dtype"])


def decode_bytes(s: Dict, contexts: Sequence[int]) -> int:
    """Bytes one decode step over sequences at ``contexts`` must read: the
    weights once and each sequence's live cache."""
    return (decode_weight_bytes(s, len(contexts))
            + sum(contexts) * kv_bytes_per_token(s))


def token_flops(s: Dict, ctx: int) -> float:
    """FLOPs to decode one token that attends to ``ctx`` positions
    (itself included): the layer matrices, attention and the output head."""
    attn = 4 * s["heads"] * s["head_dim"] * ctx
    return (2 * (s["layers"] * layer_matmul_params(s) + s["vocab"] * s["d"])
            + s["layers"] * attn)


def prefill_flops(s: Dict, prompt_len: int) -> float:
    """FLOPs to prefill one prompt: every prompt token through the layers
    with causal attention, and one row of the output head."""
    p = prompt_len
    attn = 4 * s["heads"] * s["head_dim"] * p * (p + 1) / 2
    return (2 * s["layers"] * layer_matmul_params(s) * p
            + s["layers"] * attn + 2 * s["vocab"] * s["d"])
