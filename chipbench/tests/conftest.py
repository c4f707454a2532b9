"""Tests of the benchmark, on the CPU: ``pytest chipbench/tests``.

The fixture ``tiny`` writes a cell of a few-kilobyte model (the program's
dense decoder at hidden size 64) into a temporary directory, with a backlog
mix and a Poisson mix, so a whole run fits in seconds.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIG = {
    "name": "tiny", "source": "test", "program_arch": "tinyllama-1.1b",
    "reference": "dense_decoder", "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "hidden_act": "silu", "rope_theta": 10000.0,
    "rms_norm_eps": 1e-05, "tie_word_embeddings": False,
    "max_position_embeddings": 128, "dtype": "bfloat16", "reduced": [],
}
LENGTHS = {
    "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                      "min": 4, "max": 24},
    "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 2, "max": 16},
    "block": 8, "max_len": 40, "block_size": 4,
}
TRAFFIC = {
    "tiny-backlog": {"arrival": {"process": "backlog", "requests": 24},
                     "slots": 3, "window_end": "stop_intake", **LENGTHS},
    "tiny-poisson": {"arrival": {"process": "poisson", "rate_per_s": 20.0},
                     "slots": 4, "window_end": "drain", **LENGTHS},
}
# the real cells' metrics, pointed at the tiny cells
RENAME = {"phi3-offline-batch": "tiny-batch",
          "nemo-chat-poisson": "tiny-chat"}


@pytest.fixture(scope="session")
def tiny(tmp_path_factory):
    """(bench dict, directory) of the tiny cells ``tiny-batch`` (backlog)
    and ``tiny-chat`` (Poisson)."""
    d = tmp_path_factory.mktemp("tiny")
    for sub in ("configs", "traffic", "limits"):
        (d / sub).mkdir()
    (d / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    for name, mix in TRAFFIC.items():
        (d / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    for cell in RENAME.values():
        (d / "limits" / f"{cell}.json").write_text(
            json.dumps({"max_gap": {"limit": 0.25}}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                         "file": "configs/tiny.json", "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny-batch", "config": "tiny", "traffic": "tiny-backlog",
         "chips": 1, "why": "test"},
        {"name": "tiny-chat", "config": "tiny", "traffic": "tiny-poisson",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [RENAME[w] for w in m["workloads"]]
    return bench, d
