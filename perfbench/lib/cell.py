"""A cell of `BENCHMARK.json`, resolved to its files: the configuration,
the traffic mix, its metrics, and the limits of its output check."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

from . import model_config, traffic

ROOT = Path(__file__).resolve().parents[2]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: model_config.ModelConfig
    traffic: dict
    end_to_end: list       # BENCHMARK.json metric entries of this cell
    per_layer: list
    limits: dict           # {check name: limit}


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, manifest: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = json.loads(Path(manifest).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {manifest.name}; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    path = ROOT / conf["file"]
    raw = json.loads(path.read_text())
    return Cell(
        name=name, chips=int(w["chips"]),
        config=model_config.load(path, conf["name"]),
        traffic=traffic.load(w["traffic"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        limits=raw["check"]["limits"])
