"""What a cell is, read from ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one model family, one
traffic mix or one metric lives in a file of its own, found by name:

* ``chipbench/configs/<config>.json``: the model's sizes under their
  published keys (``file`` in ``BENCHMARK.json``);
* ``chipbench/families/<family>.py`` and ``chipbench/references/<family>.py``,
  ``<family>`` being the configuration's ``reference`` key: what the
  benchmark knows of the family's shape (sizes, the program's
  configuration and parameter layout, weights, work counts), and its plain
  reference;
* ``chipbench/traffic/<traffic>.json``: the mix the generator reads;
* ``chipbench/metrics/<metric>.py``: the reader of one metric, or of every
  metric whose name starts with ``<metric>.``;
* ``chipbench/limits/<cell>.json``: the limits of the numbers that decide
  ``correct`` in that cell, with the readings they were set from.

A later cell or family adds files and entries; no code changes.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, Optional, Tuple

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

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
    bench_dir: Path = BENCH_DIR
    family: Any = None  # the module families/<reference>.py
    reference: Any = None  # the module references/<reference>.py


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
    and limits under ``bench_dir``), and its family and reference modules
    (``find_module``)."""
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
                limits=limits, bench_dir=bench_dir,
                family=find_module("families", config["reference"],
                                   bench_dir),
                reference=find_module("references", config["reference"],
                                      bench_dir))


def _find(kind: str, stem: str, bench_dir: Path) -> Optional[Path]:
    """``<kind>/<stem>.py`` in ``chipbench/``, else in ``bench_dir``."""
    for d in (BENCH_DIR, Path(bench_dir)):
        if (d / kind / f"{stem}.py").is_file():
            return d / kind / f"{stem}.py"
    return None


@functools.lru_cache(maxsize=None)
def _load(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_module(kind: str, stem: str, bench_dir: Path = BENCH_DIR):
    """The module ``<kind>/<stem>.py`` (``families``, ``references`` or
    ``metrics``), from ``chipbench/`` or else from ``bench_dir``; each file
    is loaded once."""
    path = _find(kind, stem, bench_dir)
    if path is None:
        raise SpecError(f"no {kind}/{stem}.py under {BENCH_DIR} or "
                        f"{bench_dir}")
    return _load(path)


def metric_reader(name: str, bench_dir: Path = BENCH_DIR
                  ) -> Callable[[Any], Optional[float]]:
    """``read(run) -> value or None`` from ``metrics/<name>.py``, else from
    ``metrics/<base>.py`` where ``base`` is ``name`` up to its first dot
    (one reader serves ``prefill_ms.ttft`` and ``prefill_ms.tput``)."""
    for stem in (name, name.split(".", 1)[0]):
        path = _find("metrics", stem, bench_dir)
        if path is not None:
            return _load(path).read
    raise SpecError(f"no reader for metric {name!r} under {BENCH_DIR} or "
                    f"{bench_dir}")
