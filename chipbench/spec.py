"""What a cell is, read from ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one metric
lives in a file of its own, found by name:

* ``chipbench/configs/<config>.json``: the model's sizes under their
  published keys (``file`` in ``BENCHMARK.json``);
* ``chipbench/traffic/<traffic>.json``: the mix the generator reads;
* ``chipbench/metrics/<metric>.py``: the reader of one metric, or of every
  metric whose name starts with ``<metric>.``;
* ``chipbench/limits/<cell>.json``: the limits of the numbers that decide
  ``correct`` in that cell, with the readings they were set from.

A later cell adds files and entries; no code changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, Optional, Tuple

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

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


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    moves: Optional[str] = None  # per-layer metrics only
    workloads: Optional[Tuple[str, ...]] = None  # None: every cell


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int
    end_to_end: Tuple[Metric, ...]
    per_layer: Tuple[Metric, ...]
    limits: Dict[str, Dict[str, Any]]


class SpecError(ValueError):
    """A cell, file or metric the benchmark cannot find or read."""


def _read_json(path: Path) -> Any:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def _metric(entry: dict) -> Metric:
    wl = entry.get("workloads")
    return Metric(name=entry["name"], unit=entry["unit"],
                  moves=entry.get("moves"),
                  workloads=tuple(wl) if wl is not None else None)


def load_cell(name: str, root: Path = ROOT, *,
              bench: Optional[dict] = None,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` (or of ``bench``) with
    its configuration, traffic and limits read from their files (traffic
    and limits under ``bench_dir``)."""
    if bench is None:
        bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(known: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[w["config"]]
    config = _read_json(root / centry["file"])
    traffic = _read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    lpath = bench_dir / "limits" / f"{name}.json"
    limits = _read_json(lpath) if lpath.is_file() else {}
    e2e = [_metric(m) for m in bench["end_to_end"]]
    mine = tuple(m for m in e2e if m.workloads is None or name in m.workloads)
    reported = {m.name for m in mine}
    per_layer = tuple(
        m for m in map(_metric, bench["per_layer"])
        if (name in m.workloads if m.workloads is not None
            else m.moves in reported))
    return Cell(name=name, config=config, traffic=traffic,
                chips=int(w["chips"]), end_to_end=mine, per_layer=per_layer,
                limits=limits)


def metric_reader(name: str) -> Callable[[Any], Optional[float]]:
    """``read(run) -> value or None`` from ``metrics/<name>.py``, else from
    ``metrics/<base>.py`` where ``base`` is ``name`` up to its first dot
    (one reader serves ``prefill_ms.ttft`` and ``prefill_ms.tput``)."""
    for stem in (name, name.split(".", 1)[0]):
        path = BENCH_DIR / "metrics" / f"{stem}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(
                f"chipbench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise SpecError(f"no reader for metric {name!r} under "
                    f"{BENCH_DIR / 'metrics'}")


def program_config(config: Dict[str, Any]):
    """The program's ModelConfig for a configuration file: the registered
    architecture ``program_arch`` with every published size of the file
    written over it, so what runs is what the file states."""
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


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The plain sizes the reference, the weights and the cost functions
    read, with the head size resolved the published way."""
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
