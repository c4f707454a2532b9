"""The table of device peaks, and the least time of a decode step from the
work its configuration's family counts (``families/<name>.py``:
``decode_bytes`` and ``token_flops``)."""

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


def dtype_bytes(dtype: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[dtype]


def decode_step_least_s(family, s: Dict, contexts: Iterable[int],
                        pk: Dict[str, float]) -> float:
    """Least time one decode step over sequences at ``contexts`` could take:
    the larger of the bytes it must read over the memory bandwidth and its
    FLOPs over the peak."""
    ctx = list(contexts)
    flops = sum(family.token_flops(s, c) for c in ctx)
    return max(family.decode_bytes(s, ctx) / pk["hbm_bytes_per_s"],
               flops / pk["bf16_flops_per_s"])
