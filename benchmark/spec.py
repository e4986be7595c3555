"""Resolve a cell of BENCHMARK.json into its files, by name.

A later PR adds a configuration, a traffic mix, a cell, a per-layer metric
or a model architecture as new files and new entries; nothing here names
one.
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def arch_of(config: dict):
    """The architecture module a configuration file names under ``arch``
    (a dotted module path): the model's builder, plain reference and
    counts, under the fixed names of ``benchmark/archs/``'s contract."""
    if "arch" not in config:
        raise SystemExit(f"benchmark: configuration {config.get('name')!r} "
                         "names no 'arch' module")
    return importlib.import_module(config["arch"])


def metrics_of(entries: list[dict], cell: str) -> list[dict]:
    """The metrics of ``entries`` that the cell reports: those with no
    ``workloads`` key, and those that list it."""
    return [m for m in entries if cell in m.get("workloads", [cell])]


def resolve_cell(name: str, root: Path = ROOT) -> dict:
    """Everything one run of the cell ``name`` needs, found by name."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r} in "
                         f"BENCHMARK.json (has: {sorted(cells)})")
    cell = cells[name]
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == cell["config"])
    home = root / bench["paths"][0]
    per_layer = []
    for m in metrics_of(bench["per_layer"], name):
        reader = load_json(home / "layer_metrics" / f"{m['name']}.json")
        per_layer.append({**m, **reader})
    return {
        "cell": cell,
        "config": load_json(root / config_entry["file"]),
        "traffic": load_json(home / "traffic" / f"{cell['traffic']}.json"),
        "end_to_end": metrics_of(bench["end_to_end"], name),
        "per_layer": per_layer,
    }
