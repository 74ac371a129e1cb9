"""Resolve a cell of ``BENCHMARK.json`` into its parts, each by file name:

* the configuration ``bench/configs/<config>.json`` (and the reference it
  names, ``bench/references/<reference>.py``);
* the traffic mix ``bench/traffic/<traffic>.json``;
* one reader ``bench/metrics/<metric>.py`` per metric the cell reports: its
  end-to-end metrics, and the per-layer metrics that list the cell or, with
  no list, move one of its end-to-end metrics.

A later cell, configuration, mix or metric is new files and new entries.
"""
from __future__ import annotations

import importlib
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

from bench import ROOT
from bench.traffic import load_mix

CONFIG_DIR = Path(__file__).resolve().parent / "configs"


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


def load_config(name: str) -> dict:
    path = CONFIG_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration {name!r} at {path}")
    return json.loads(path.read_text())


def reader(metric: str):
    return importlib.import_module(f"bench.metrics.{metric}")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    def readers(self, trace: bool) -> Dict[str, object]:
        specs = self.per_layer if trace else self.end_to_end
        return {m["name"]: reader(m["name"]) for m in specs}


def metrics_of(bench: dict, cell: str):
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def resolve(name: str, bench: dict | None = None) -> Cell:
    bench = bench or load_benchmark()
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    e2e, layer = metrics_of(bench, name)
    return Cell(name, entry["chips"], load_config(entry["config"]),
                load_mix(entry["traffic"]), e2e, layer)
