"""Find a cell's files by the names in `BENCHMARK.json`."""

from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


class Cell:
    """One entry of `workloads` with its configuration and traffic files."""

    def __init__(self, manifest: dict, name: str, rehearse: bool = False):
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; has {sorted(cells)}")
        self.manifest = manifest
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in manifest["configs"]}[self.entry["config"]]
        self.config_name = cfg_entry["name"]
        self.config = load_json(os.path.join(ROOT, cfg_entry["file"]))
        self.traffic_name = self.entry["traffic"]
        self.traffic = load_json(os.path.join(
            BENCH_DIR, "traffic", f"{self.traffic_name}.json"))
        if int(self.traffic["chips"]) != self.chips:
            raise ValueError(f"{name}: traffic file says {self.traffic['chips']} "
                             f"chips, BENCHMARK.json {self.chips}")
        self.limits = load_json(os.path.join(
            BENCH_DIR, "limits", f"{name}.json"))["limits"]
        self.rehearse = rehearse
        if rehearse:
            over = load_json(os.path.join(BENCH_DIR, "rehearsal", "overrides.json"))
            self.config = _merge(self.config, over["config"])
            self.traffic = _merge(self.traffic, over["traffic"])
            self.limits = _merge(self.limits, over["limits"])

    def metrics(self, group: str) -> list[dict]:
        """The cell's metrics of `end_to_end` or `per_layer`: those with no
        `workloads` key and those that list this cell."""
        return [m for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def module(self, package: str, name: str):
        return importlib.import_module(f"perfbench.{package}.{name}")
