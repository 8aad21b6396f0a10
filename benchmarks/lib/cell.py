"""What one run is asked to do, read from ``BENCHMARK.json`` and the data
files it names. Nothing here touches JAX."""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it points at."""
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]          # the configuration file, as run
    traffic_name: str
    traffic: Dict[str, Any]         # the traffic file
    end_to_end: List[Dict[str, Any]]    # metric entries this cell reports
    per_layer: List[Dict[str, Any]]


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _in_cell(metric: Dict[str, Any], name: str) -> bool:
    return "workloads" not in metric or name in metric["workloads"]


def reports(per_layer_metric: Dict[str, Any], cell_name: str,
            end_to_end: List[Dict[str, Any]]) -> bool:
    """A per-layer metric belongs to a cell it lists, or, without a list,
    to every cell that reports the end-to-end metric it moves."""
    if "workloads" in per_layer_metric:
        return cell_name in per_layer_metric["workloads"]
    return any(m["name"] == per_layer_metric["moves"] for m in end_to_end)


def load_cell(workload: str, root: str = ROOT) -> Cell:
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"benchmark: no workload {workload!r} in BENCHMARK.json "
            f"(have {sorted(cells)})")
    w = cells[workload]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(
        root, os.path.dirname(cfg_entry["file"]), "..", "traffic",
        w["traffic"] + ".json"))
    e2e = [m for m in spec["end_to_end"] if _in_cell(m, workload)]
    per_layer = [m for m in spec["per_layer"]
                 if reports(m, workload, e2e)]
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer)
