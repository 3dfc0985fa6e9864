"""A benchmark cell, found by name.

``BENCHMARK.json`` names the cell's configuration and traffic mix; the
configuration is ``bench/configs/<name>.json`` and names its controller,
whose program adapter is ``bench/controllers/<controller>.py`` and whose
plain reference is ``bench/reference/<controller>.py``; the mix is
``bench/traffic/<name>.json``; each per-layer metric is
``bench/metrics/<name>.py``. Adding any of them is adding files and
entries: nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def controller(self):
        return importlib.import_module(
            f"bench.controllers.{self.config['controller']}")

    @property
    def reference(self):
        return importlib.import_module(
            f"bench.reference.{self.config['controller']}")

    def metric_reader(self, name: str):
        return importlib.import_module(f"bench.metrics.{name}")


def _listed(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, spec_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = json.loads(Path(spec_path).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {spec_path.name}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    if len(traffic["vms"]) != config["num_vms"]:
        raise ValueError(f"{name}: traffic {w['traffic']} has "
                         f"{len(traffic['vms'])} VMs, configuration "
                         f"{w['config']} {config['num_vms']}")
    return Cell(name, w["chips"], config, traffic,
                [m for m in spec["end_to_end"] if _listed(m, name)],
                [m for m in spec["per_layer"] if _listed(m, name)])
