"""Operations and bytes of the work a served request needs, computed from the
configuration's sizes, and the table of device peaks.

Only useful work counts: rows a program pads or masks, logits it computes
for prompt positions nobody reads, and cache it reads past a sequence's
length are not in these numbers, so a change that removes them raises a
share and cannot push it past 100%.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS} (known: {sorted(table)})")
    return table[device_kind]


def _bytes(dtype: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[dtype]


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
    return (param_count(s) - norms) * _bytes(s["dtype"]) + 4 * norms


def decode_weight_bytes(s: Dict, rows: int) -> int:
    """Weight bytes one decode step must read: every layer matrix, the norms
    and the output head once, and one embedding row per sequence."""
    b = _bytes(s["dtype"])
    norms = (2 * s["layers"] + 1) * s["d"]
    return (s["layers"] * layer_matmul_params(s) * b + 4 * norms
            + s["vocab"] * s["d"] * b + rows * s["d"] * b)


def kv_bytes_per_token(s: Dict) -> int:
    """Key and value bytes one token keeps in the cache, over all layers."""
    return s["layers"] * 2 * s["kv_heads"] * s["head_dim"] * _bytes(s["dtype"])


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


def decode_step_least_s(s: Dict, contexts: Iterable[int],
                        pk: Dict[str, float]) -> float:
    """Least time one decode step over sequences at ``contexts`` could take:
    the larger of its bytes (weights once, live cache) over the memory
    bandwidth and its FLOPs over the peak."""
    ctx = list(contexts)
    nbytes = (decode_weight_bytes(s, len(ctx))
              + sum(ctx) * kv_bytes_per_token(s))
    flops = sum(token_flops(s, c) for c in ctx)
    return max(nbytes / pk["hbm_bytes_per_s"], flops / pk["bf16_flops_per_s"])
