"""Whole runs of a tiny latent-attention expert cell (the
``mla_moe_decoder`` family) on the CPU: a sound run is correct; the float8
control and a program that drops one held expert's output are not; a
traced run's expert counts reach the span metric that reads them."""

import json
import time
from pathlib import Path

import pytest

from chipbench import harness, spec

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 77
CELL = "tiny-moe"
# the published Moonlight keys at widths a test can run: 3 layers (one
# dense), 8 of 64 routed experts held, 6 per token, 2 shared.  Served in
# float32: at hidden size 64 bfloat16 rounding flips enough top-6 choices
# against the float32 reference to move a tiny model's logits by half a
# standard deviation (at 2048 the rounding is relatively smaller)
TINY_MOE = {
    **json.loads((ROOT / "chipbench/configs/moonlight-16b-a3b-e8.json")
                 .read_text()),
    "name": "tiny-moe", "hidden_size": 64, "intermediate_size": 128,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 3, "vocab_size": 256,
    "max_position_embeddings": 128, "dtype": "float32",
}
TRAFFIC = {
    "arrival": {"process": "backlog", "requests": 24},
    "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                      "min": 4, "max": 24},
    "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 2, "max": 16},
    "block": 8, "slots": 4, "max_len": 40, "block_size": 4,
    "window_end": "stop_intake",
}


@pytest.fixture(scope="module")
def moe_cell(tmp_path_factory):
    d = tmp_path_factory.mktemp("tiny_moe")
    for sub in ("configs", "traffic", "limits"):
        (d / sub).mkdir()
    (d / "configs" / "tiny-moe.json").write_text(json.dumps(TINY_MOE))
    (d / "traffic" / "tiny-moe.json").write_text(json.dumps(TRAFFIC))
    (d / "limits" / f"{CELL}.json").write_text(
        json.dumps({"max_gap": {"limit": 0.25}}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny-moe", "source": "test",
                         "reduced": ["n_routed_experts"],
                         "file": "configs/tiny-moe.json", "why": "test"}]
    bench["workloads"] = [{"name": CELL, "config": "tiny-moe",
                           "traffic": "tiny-moe", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if any(
                w.startswith("moonlight") for w in m["workloads"]) else []
    return spec.load_cell(CELL, d, bench=bench, bench_dir=d)


def run(cell, control=False, trace=False):
    return harness.run_cell(cell, seed=SEED, seconds=2.0, trace=trace,
                            t_start=time.perf_counter(), require_chip=False,
                            control=control, log=lambda msg: None)


def test_sound_run_is_correct(moe_cell):
    out = run(moe_cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["readings"]["compared_tokens"] >= 50
    assert set(out["metrics"]) == {m.name for m in moe_cell.end_to_end}


def test_control_is_not_correct(moe_cell):
    out = run(moe_cell, control=True)
    gap = out["checks"]["max_gap"]
    assert not out["correct"], out["checks"]
    assert gap["value"] == out["readings"]["max_gap_fp8"] > gap["limit"]
    assert out["readings"]["max_gap"] < gap["limit"]


def test_dropped_held_expert_is_caught(moe_cell, monkeypatch):
    """The program computes held expert 0's output as zero in every
    expert layer: the comparison with the reference sees it."""
    from repro.models import ffn

    orig = ffn._held_part

    def drop_first(params, *a, **kw):
        return orig({**params, "w_out": params["w_out"].at[0].set(0)},
                    *a, **kw)

    monkeypatch.setattr(ffn, "_held_part", drop_first)
    out = run(moe_cell)
    assert not out["correct"], out["checks"]
    assert out["checks"]["max_gap"]["value"] > \
        out["checks"]["max_gap"]["limit"]


def test_traced_run_reads_the_expert_counts(moe_cell):
    """A traced run's admissions carry ``held_assignments``, which the
    useful-share reader turns into a share of the pairs computed; the
    device-time metrics stay silent on the CPU, where there is no device
    trace."""
    out = run(moe_cell, trace=True)
    r = out["run"]
    admits = [s for s in r.spans
              if s.name == "serve/admit" and "rids" in s.attrs]
    assert admits and all(s.attrs["held_assignments"] > 0
                          and s.attrs["experts_hit"] > 0 for s in admits)
    share = out["metrics"]["expert_useful_share.moe"]["value"]
    useful = sum(s.attrs["useful_tokens"] for s in admits)
    padded = sum(s.attrs["rows"] * s.attrs["padded_len"] for s in admits)
    # routed pairs on held experts per real token average 6 x 8 / 64
    assert 0 < share < 100 * useful / padded
    assert "mla_roofline.moe" not in out["metrics"]
