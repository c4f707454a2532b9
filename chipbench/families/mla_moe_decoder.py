"""The latent-attention expert decoder family (DeepSeek-V3 layout:
Moonlight-16B-A3B): every part of the benchmark that knows a
configuration's shape, as ``families/dense_decoder.py`` describes.

A layer is latent attention (MLA) and then an MLP: the first
``first_k_dense_replace`` layers a dense gated MLP, the rest an expert
layer (a router over every routed expert, the experts held here, and the
shared experts as one MLP).  The configuration's ``n_routed_experts`` is
what this chip holds (``published`` keeps the router's width); the held
experts are the first ones.

The work counts take these choices:

* ``decode_bytes``: every weight once, except the held experts, which are
  counted at the number a step is expected to reach under uniform routing
  at its live rows, ``held (1 - ((n - k) / n) ** rows)`` per expert layer
  (``n`` routed experts, ``k`` per token); plus each live cached token's
  latent entry, ``kv_lora_rank + qk_rope_head_dim`` values per layer, and
  one embedding row per sequence.
* ``token_flops`` and ``prefill_flops``: attention in the absorbed form
  (queries turned into the latent space, scores against the latent and the
  rope key, the output turned back per head), ``k x held / n`` routed
  experts per token (the held share of its choices), the shared experts,
  the router, the dense layers and one row of the output head.
* ``mla_*`` and ``expert_*``: the same counts cut to the latent attention
  and to the expert layers, for the metrics that read the device seconds
  under the program's ``mla`` and ``moe_*`` scopes.

Only useful work counts, as in the dense family: padded rows, masked
slots, experts that no live token reached and cache past a sequence's
length are not in these numbers.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

from chipbench.costs import dtype_bytes
from chipbench.spec import SpecError

# the published keys whose values the program supports only as given
SUPPORTED = {"q_lora_rank": None, "n_group": 1, "topk_group": 1,
             "topk_method": "noaux_tc", "norm_topk_prob": True,
             "moe_layer_freq": 1, "num_nextn_predict_layers": 0,
             "hidden_act": "silu", "attention_bias": False}


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    for key, want in SUPPORTED.items():
        if config.get(key, want) != want:
            raise SpecError(f"{key}={config[key]!r} is not supported "
                            f"(only {want!r})")
    routed = config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"])
    return {
        "layers": config["num_hidden_layers"],
        "dense_layers": config["first_k_dense_replace"],
        "d": config["hidden_size"], "heads": config["num_attention_heads"],
        "nope": config["qk_nope_head_dim"], "rope": config["qk_rope_head_dim"],
        "v_dim": config["v_head_dim"], "rank": config["kv_lora_rank"],
        "dense_ff": config["intermediate_size"],
        "expert_ff": config["moe_intermediate_size"],
        "experts": routed, "held": config["n_routed_experts"],
        "top_k": config["num_experts_per_tok"],
        "shared": config["n_shared_experts"],
        "routed_scale": float(config["routed_scaling_factor"]),
        "scoring": config["scoring_func"],
        "vocab": config["vocab_size"],
        "rope_theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "tied": bool(config.get("tie_word_embeddings", False)),
        "dtype": config.get("dtype", "bfloat16"),
    }


def program_config(config: Dict[str, Any]):
    """The registered architecture with every size of the file written
    over it, holding experts 0 .. held - 1 of the router's."""
    from repro.configs import get_config

    s = sizes(config)
    return dataclasses.replace(
        get_config(config["program_arch"]),
        n_layers=s["layers"], d_model=s["d"], n_heads=s["heads"],
        n_kv_heads=config["num_key_value_heads"], d_ff=s["expert_ff"],
        vocab_size=s["vocab"], activation="swiglu", n_experts=s["experts"],
        experts_per_token=s["top_k"], moe_shared_experts=s["shared"],
        moe_scoring=s["scoring"], routed_scaling=s["routed_scale"],
        held_expert_count=s["held"],
        first_k_dense=s["dense_layers"], dense_d_ff=s["dense_ff"],
        kv_lora_rank=s["rank"], qk_nope_head_dim=s["nope"],
        qk_rope_head_dim=s["rope"], v_head_dim=s["v_dim"],
        rope_theta=s["rope_theta"], norm_eps=s["eps"],
        tie_embeddings=s["tied"],
        max_seq_len=config["max_position_embeddings"], dtype=s["dtype"])


# an MLA layer's leaves: name -> (shape without the layer axis, fan-in)
def _attn_leaves(s: Dict) -> Dict[str, tuple]:
    d, h, r = s["d"], s["heads"], s["rank"]
    return {
        "attn_norm": ((d,), "norm"),
        "wq": ((d, h, s["nope"] + s["rope"]), d),
        "wkv_a": ((d, r + s["rope"]), d),
        "kv_norm": ((r,), "norm"),
        "wkv_b": ((r, h, s["nope"] + s["v_dim"]), r),
        "wo": ((h * s["v_dim"], d), h * s["v_dim"]),
        "mlp_norm": ((d,), "norm"),
    }


def leaf_shapes(s: Dict) -> Dict[str, tuple]:
    """name -> (shape, scale), as ``families/dense_decoder.py`` gives them:
    dense layer ``i``'s leaves as ``dense<i>_<leaf>``, the expert layers'
    stacked over those layers."""
    d, v = s["d"], s["vocab"]
    n = s["layers"] - s["dense_layers"]
    e, f, fs = s["held"], s["expert_ff"], s["shared"] * s["expert_ff"]
    dense = {**_attn_leaves(s),
             "w_gate": ((d, s["dense_ff"]), d),
             "w_up": ((d, s["dense_ff"]), d),
             "w_down": ((s["dense_ff"], d), s["dense_ff"])}
    moe = {**_attn_leaves(s),
           "router": ((d, s["experts"]), d),
           "router_bias": ((s["experts"],), None),
           "w_gate": ((e, d, f), d), "w_up": ((e, d, f), d),
           "w_down": ((e, f, d), f),
           "shared_gate": ((d, fs), d), "shared_up": ((d, fs), d),
           "shared_down": ((fs, d), fs)}
    out = {"embed": ((v, d), None), "lm_head": ((v, d), None),
           "final_norm": ((d,), "norm")}
    for i in range(s["dense_layers"]):
        out.update({f"dense{i}_{name}": leaf for name, leaf in dense.items()})
    out.update({name: ((n,) + shape, fan)
                for name, (shape, fan) in moe.items()})
    return out


def _f32(a):
    """``a`` (an array or a shape) in float32."""
    if isinstance(a, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct(a.shape, jnp.float32)
    return a.astype(jnp.float32)


def _layer(w: Dict, prefix: str) -> Dict:
    """The program's attention and norm leaves of the leaves ``prefix*``."""
    return {"ln1": {"scale": w[prefix + "attn_norm"]},
            "ln2": {"scale": w[prefix + "mlp_norm"]},
            "attn": {"wq": w[prefix + "wq"], "wkv_a": w[prefix + "wkv_a"],
                     "kv_norm": {"scale": w[prefix + "kv_norm"]},
                     "wkv_b": w[prefix + "wkv_b"], "wo": w[prefix + "wo"]}}


def program_params(w: Dict, model) -> Dict:
    """The drawn weights in the program's parameter layout: ``layer_<i>``
    for each dense layer, ``scan`` for the stacked expert layers; the
    router and its bias in float32, as the program keeps them."""
    layers = {}
    for i in range(model.cfg.first_k_dense):
        p = f"dense{i}_"
        layers[f"layer_{i}"] = {
            **_layer(w, p),
            "ffn": {"w_in": w[p + "w_up"], "w_gate": w[p + "w_gate"],
                    "w_out": w[p + "w_down"]}}
    layers["scan"] = {
        **_layer(w, ""),
        "ffn": {"router": _f32(w["router"]),
                "router_bias": _f32(w["router_bias"]),
                "w_in": w["w_up"], "w_gate": w["w_gate"],
                "w_out": w["w_down"],
                "shared": {"w_in": w["shared_up"], "w_gate": w["shared_gate"],
                           "w_out": w["shared_down"]}}}
    return {"embed": w["embed"], "unembed": w["lm_head"],
            "final_norm": {"scale": w["final_norm"]}, "layers": layers}


# ------------------------------------------------------------ work counts --


def mla_params(s: Dict) -> int:
    """Matrix parameters of one layer's latent attention."""
    d, h, r = s["d"], s["heads"], s["rank"]
    return (d * h * (s["nope"] + s["rope"]) + d * (r + s["rope"])
            + r * h * (s["nope"] + s["v_dim"]) + h * s["v_dim"] * d)


def expert_params(s: Dict) -> int:
    return 3 * s["d"] * s["expert_ff"]


def moe_fixed_params(s: Dict) -> int:
    """One expert layer's shared MLP and router."""
    return s["shared"] * expert_params(s) + s["d"] * s["experts"]


def _moe_layers(s: Dict) -> int:
    return s["layers"] - s["dense_layers"]


def param_count(s: Dict) -> int:
    """Every parameter held: layers (held experts), norms, the router's
    bias, embedding and output head."""
    k, n = s["dense_layers"], _moe_layers(s)
    norms = (2 * s["layers"] + 1) * s["d"] + s["layers"] * s["rank"]
    tables = s["vocab"] * s["d"] * (1 if s["tied"] else 2)
    return (s["layers"] * mla_params(s) + k * 3 * s["d"] * s["dense_ff"]
            + n * (s["held"] * expert_params(s) + moe_fixed_params(s)
                   + s["experts"])
            + norms + tables)


def weight_bytes(s: Dict) -> int:
    """Bytes of every weight as served (norm gains, router and bias in
    float32)."""
    n = _moe_layers(s)
    f32 = ((2 * s["layers"] + 1) * s["d"] + s["layers"] * s["rank"]
           + n * (s["d"] * s["experts"] + s["experts"]))
    return (param_count(s) - f32) * dtype_bytes(s["dtype"]) + 4 * f32


def experts_reached(s: Dict, rows: int) -> float:
    """Held experts one expert layer's step is expected to reach when
    ``rows`` tokens each choose ``top_k`` of the routed experts
    uniformly."""
    miss = (s["experts"] - s["top_k"]) / s["experts"]
    return s["held"] * (1 - miss ** rows)


def latent_bytes_per_token(s: Dict) -> int:
    """Latent cache bytes one token keeps, over all layers."""
    return s["layers"] * (s["rank"] + s["rope"]) * dtype_bytes(s["dtype"])


def mla_weight_bytes(s: Dict) -> int:
    b = dtype_bytes(s["dtype"])
    return s["layers"] * (mla_params(s) * b + 4 * s["rank"])


def expert_bytes(s: Dict, experts_hit: float, layer_steps: int) -> float:
    """Bytes the expert layers must read: ``experts_hit`` held experts
    (summed over layers and steps), and the shared MLP and the router
    (float32) in each of ``layer_steps`` expert-layer steps."""
    b = dtype_bytes(s["dtype"])
    return (experts_hit * expert_params(s) * b
            + layer_steps * (s["shared"] * expert_params(s) * b
                             + 4 * (s["d"] + 1) * s["experts"]))


def decode_bytes(s: Dict, contexts: Sequence[int]) -> float:
    """Bytes one decode step over sequences at ``contexts`` must read."""
    b = dtype_bytes(s["dtype"])
    rows = len(contexts)
    n = _moe_layers(s)
    norms = 4 * ((2 * s["layers"] + 1) * s["d"])
    dense = s["dense_layers"] * 3 * s["d"] * s["dense_ff"] * b
    return (mla_weight_bytes(s) + dense + norms
            + expert_bytes(s, n * experts_reached(s, rows), n)
            + s["vocab"] * s["d"] * b + rows * s["d"] * b
            + sum(contexts) * latent_bytes_per_token(s))


def mla_flops(s: Dict, ctx: int) -> float:
    """One token's latent attention over ``ctx`` positions, one layer, in
    the absorbed form."""
    h, r, e = s["heads"], s["rank"], s["rope"]
    absorb = 2 * h * s["nope"] * r + 2 * h * r * s["v_dim"]
    return 2 * mla_params(s) + absorb + 2 * h * (r + e + r) * ctx


def _ffn_flops(s: Dict) -> float:
    """One token's MLPs over the whole stack: dense layers, and in each
    expert layer the held share of its routed experts, the shared experts
    and the router."""
    routed = s["top_k"] * s["held"] / s["experts"]
    per_moe = 2 * ((routed + s["shared"]) * expert_params(s)
                   + s["d"] * s["experts"])
    return (s["dense_layers"] * 2 * 3 * s["d"] * s["dense_ff"]
            + _moe_layers(s) * per_moe)


def token_flops(s: Dict, ctx: int) -> float:
    """FLOPs to decode one token that attends to ``ctx`` positions (itself
    included)."""
    return (s["layers"] * mla_flops(s, ctx) + _ffn_flops(s)
            + 2 * s["vocab"] * s["d"])


def prefill_flops(s: Dict, prompt_len: int) -> float:
    """FLOPs to prefill one prompt: every token through the layers with
    causal attention, and one row of the output head."""
    p = prompt_len
    h, r, e = s["heads"], s["rank"], s["rope"]
    per_token = s["layers"] * (mla_flops(s, 0)) + _ffn_flops(s)
    pairs = p * (p + 1) / 2
    return (per_token * p + s["layers"] * 2 * h * (r + e + r) * pairs
            + 2 * s["vocab"] * s["d"])
