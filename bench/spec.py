"""Find a cell and everything that belongs to it by the names in
``BENCHMARK.json``: its configuration file, its traffic file
(``bench/traffic/<traffic>.json``), its limits
(``bench/limits/<workload>.json``) and the readers of its metrics
(``bench/metrics/<metric>.py``).  Nothing here names a cell, so a new cell
or metric is new files and new entries, and no edit."""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, List


@dataclass
class CellSpec:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in reported if "moves" in metric else True


def load(root: Path, workload: str) -> CellSpec:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    limits_file = root / "bench" / "limits" / f"{workload}.json"
    limits = json.loads(limits_file.read_text()) if limits_file.exists() else {}
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, workload, reported)]
    return CellSpec(workload, int(w["chips"]), config, traffic, limits, e2e,
                    per_layer, root)


def reader(root: Path, metric: str) -> Callable:
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
