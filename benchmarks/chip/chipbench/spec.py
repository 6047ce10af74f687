"""Finds everything that belongs to one cell by the names in
``BENCHMARK.json``: the configuration file, the traffic file, the cell's
file (its page pool and its limit), the plain reference and the per-layer
metric readers.  Adding a configuration, a traffic mix, a cell or a metric
means adding files and entries; nothing here names one."""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parents[1]          # benchmarks/chip
ROOT = HERE.parents[1]


@dataclass
class Cell:
    name: str
    config: dict                  # configs/<file>: source, model, reduced, ...
    traffic: dict                 # traffic/<traffic>.json
    data: dict                    # cells/<name>.json: pool size, limits
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]         # the metrics this cell reports

    @property
    def model(self) -> dict:
        return self.config["model"]


def load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, bench_file: Optional[Path] = None) -> Cell:
    bench = json.loads((bench_file or ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((ROOT / cfg["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    data = json.loads((HERE / "cells" / f"{workload}.json").read_text())

    def applies(m: dict) -> bool:
        return "workloads" not in m or workload in m["workloads"]

    return Cell(name=workload, config=config, traffic=traffic, data=data,
                chips=w["chips"],
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


def reference(cell: Cell) -> ModuleType:
    return load_module(HERE / "references" / f"{cell.config['reference']}.py")


def readers(cell: Cell) -> Dict[str, ModuleType]:
    return {m["name"]: load_module(HERE / "metrics" / f"{m['name']}.py")
            for m in cell.per_layer}
