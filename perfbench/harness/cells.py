"""Finding a cell's files by the names in BENCHMARK.json."""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # perfbench/
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(
        f"no workload {name!r} in BENCHMARK.json (has {[w['name'] for w in bench['workloads']]})"
    )


def config_of(bench: Dict[str, Any], name: str, root: str = ROOT) -> Dict[str, Any]:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(root, c["file"]))
    raise SystemExit(f"no configuration {name!r} in BENCHMARK.json")


def traffic_of(name: str) -> Dict[str, Any]:
    return load_json(os.path.join(HERE, "traffic", f"{name}.json"))


def metrics_of(bench: Dict[str, Any], cell_name: str, group: str) -> List[Dict[str, Any]]:
    """The metrics of ``group`` (``end_to_end`` / ``per_layer``) that this
    cell reports: those with no ``workloads`` key, and those listing it."""
    return [
        m for m in bench[group]
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def layer_metric_spec(name: str) -> Dict[str, Any]:
    return load_json(os.path.join(HERE, "layer_metrics", f"{name}.json"))
