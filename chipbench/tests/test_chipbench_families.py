"""A configuration's family: the weights it draws, and a family brought by
new files alone."""

import hashlib
import json
import time
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness, spec, weights

ROOT = Path(__file__).resolve().parents[2]
DENSE = spec.find_module("families", "dense_decoder")

# sha256 (first 16 hex digits) of each leaf drawn for seed 2**31 + 5 by the
# weights module as it was before families were split out, at the sizes of
# ``tiny_sizes``
BEFORE = {
    "phi3-mini-3.8b": {
        "attn_norm": "999f2fe540269e7b", "embed": "b5059b8584c1a36d",
        "final_norm": "8cbc12fbd9401dc5", "lm_head": "4801d73b46259114",
        "mlp_norm": "53ad44fe3f9f6cac", "w_down": "bd68011b331ad52d",
        "w_gate": "932b7927ee379d1e", "w_up": "ba2981981f42eac7",
        "wk": "dffd46c4d7747dab", "wo": "02e3acae58034042",
        "wq": "17ffa65bf411a403", "wv": "3f080bbb4d680f8f"},
    "mistral-nemo-12b-s10": {
        "attn_norm": "999f2fe540269e7b", "embed": "d96cc1243babb861",
        "final_norm": "8cbc12fbd9401dc5", "lm_head": "4c4ce63752ef696f",
        "mlp_norm": "53ad44fe3f9f6cac", "w_down": "4ff9c12c67da0e9f",
        "w_gate": "a82b05afa1e65814", "w_up": "09d232f237f6b6e0",
        "wk": "ca68909195eeb129", "wo": "0a6a11ef3d47e9fa",
        "wq": "85d497343780fcc5", "wv": "811cc970449caad7"},
}


def tiny_sizes(name):
    """A configuration's sizes with its leaf set, head grouping, vocabulary
    and type, at widths a test can draw."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == name)
    config = json.loads((ROOT / entry["file"]).read_text())
    family = spec.find_module("families", config["reference"])
    s = family.sizes(config)
    return family, {**s, "layers": 2, "d": 64, "heads": 4,
                    "kv_heads": 4 * s["kv_heads"] // s["heads"],
                    "head_dim": s["head_dim"] // 8, "ff": s["ff"] // 64}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_draw_is_bit_identical_to_before(name):
    family, s = tiny_sizes(name)
    w = weights.draw(family, s, 2**31 + 5)
    got = {k: hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16]
           for k, v in w.items()}
    assert got == BEFORE[name]
    shapes = weights.shapes(family, s)
    assert {k: (v.shape, v.dtype) for k, v in shapes.items()} == \
        {k: (v.shape, v.dtype) for k, v in w.items()}


def test_modules_resolve_in_chipbench_first(tmp_path):
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "dense_decoder.py").write_text("raise Error\n")
    assert spec.find_module("families", "dense_decoder", tmp_path) is DENSE
    with pytest.raises(spec.SpecError):
        spec.find_module("families", "no_such_family", tmp_path)


def test_new_family_by_files_alone(tiny, tmp_path):
    """A copy of the dense family and its reference, with one leaf renamed,
    written into the cell's own directory: the tiny cell finds both there
    and runs correct, with no file of chipbench/ changed."""
    bench, d = tiny
    for sub in ("traffic", "limits", "configs"):
        (tmp_path / sub).mkdir()
        for f in (d / sub).iterdir():
            (tmp_path / sub / f.name).write_text(f.read_text())
    config = json.loads((d / "configs" / "tiny.json").read_text())
    config["reference"] = "dense_renamed"
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps(config))
    for kind in ("families", "references"):
        (tmp_path / kind).mkdir()
        src = (spec.BENCH_DIR / kind / "dense_decoder.py").read_text()
        assert src.count('"w_up"') >= 1
        (tmp_path / kind / "dense_renamed.py").write_text(
            src.replace('"w_up"', '"w_upper"'))
    cell = spec.load_cell("tiny-chat", tmp_path, bench=bench,
                          bench_dir=tmp_path)
    assert Path(cell.family.__file__).parent == tmp_path / "families"
    assert Path(cell.reference.__file__).parent == tmp_path / "references"
    s = cell.family.sizes(cell.config)
    assert "w_upper" in cell.family.leaf_shapes(s)
    assert "w_up" not in cell.family.leaf_shapes(s)
    out = harness.run_cell(cell, seed=2**31 + 11, seconds=2.0, trace=False,
                           t_start=time.perf_counter(), require_chip=False,
                           log=lambda msg: None)
    assert out["correct"], out["checks"]
    assert out["readings"]["compared_tokens"] >= 50
