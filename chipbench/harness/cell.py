"""Find one cell of ``BENCHMARK.json`` and everything it names, by name.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  The configuration's file comes from ``configs``; the mix is
``chipbench/traffic/<traffic>.json``; the configuration's ``driver`` key
names ``chipbench/drivers/<driver>.py``; each per-layer metric is read by
``chipbench/metrics/<metric>.py``.  Adding a cell, a mix, a configuration
or a metric therefore adds files and entries and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

HERE = Path(__file__).resolve().parents[1]      # chipbench/


class CellError(RuntimeError):
    """The cell or a file it names is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]      # BENCHMARK.json metric entries of this cell
    per_layer: List[dict]


def _read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError) as e:
        raise CellError(f"cannot read {path}: {e}") from e


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(root: Path, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``."""
    bench = _read_json(Path(root) / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json; have "
                        f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if w["config"] not in configs:
        raise CellError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = _read_json(Path(root) / configs[w["config"]]["file"])
    traffic = _read_json(HERE / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=[m for m in bench.get("end_to_end", [])
                            if _applies(m, name)],
                per_layer=[m for m in bench.get("per_layer", [])
                           if _applies(m, name)])


def _module(path: Path, qualname: str) -> ModuleType:
    if not path.is_file():
        raise CellError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(qualname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(config: dict) -> ModuleType:
    """The engine driver the configuration names."""
    return _module(HERE / "drivers" / f"{config['driver']}.py",
                   f"chipbench_driver_{config['driver']}")


def metric_readers(cell: Cell) -> Dict[str, ModuleType]:
    """``{metric name: reader module}`` for the cell's per-layer metrics."""
    return {m["name"]: _module(HERE / "metrics" / f"{m['name']}.py",
                               f"chipbench_metric_{m['name']}")
            for m in cell.per_layer}
