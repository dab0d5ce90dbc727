"""Find a cell's configuration, traffic mix, reference and metric readers
by the names in ``BENCHMARK.json``. Nothing here knows a cell: adding a
cell, a mix or a metric is adding files and entries."""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]         # chipbench/
ROOT = BENCH.parent


class CellError(ValueError):
    """The benchmark files do not define what was asked for."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict                # chipbench/configs/<config>.json
    mix: dict                   # chipbench/traffic/<traffic>.json
    end_to_end: List[dict]      # BENCHMARK.json entries this cell reports
    per_layer: List[dict]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise CellError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def mix(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def reference(cfg: dict) -> ModuleType:
    return load_module(BENCH / "references" / f"{cfg['reference']}.py",
                       "ref_" + cfg["reference"])


def adapter(cfg: dict) -> ModuleType:
    kind = cfg["model"]["model_type"]
    return load_module(BENCH / "adapters" / f"{kind}.py", "adapter_" + kind)


def metric_reader(name: str) -> ModuleType:
    return load_module(BENCH / "metrics" / f"{name}.py", "metric_" + name)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find(workload: str, bench: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = load_json(bench)
    cells: Dict[str, dict] = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in {bench.name}; "
                        f"known: {sorted(cells)}")
    w = cells[workload]
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _applies(m, workload) and m["moves"] in moved]
    return Cell(name=workload, chips=int(w["chips"]),
                config=config(w["config"]), mix=mix(w["traffic"]),
                end_to_end=e2e, per_layer=per_layer)
