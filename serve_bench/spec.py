"""Finds every piece of a cell by name, from files alone.

``BENCHMARK.json`` (at the root of the checkout) names the cell's
configuration and traffic mix; then

    configs/<config>.json      the model as it is served, with its source
    traffic/<traffic>.json     the mix's parameters, read by traffic.py,
                               which finds its arrival process and length
                               laws as traffic/arrivals/<name>.py and
                               traffic/lengths/<name>.py
    workloads/<cell>.json      the cell's rate or clients, engine sizes,
                               lead-in, drain bound and check sample
    metrics/<metric>.py        one reader per per-layer metric

so a new configuration, mix, cell or metric is new files and a new entry.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    workload: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def engine(self) -> dict:
        return self.workload["engine"]


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT, here: str = HERE) -> Cell:
    bench = load_benchmark(root)
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(w['name'] for w in bench['workloads'])}")
    e2e = [m for m in bench["end_to_end"] if reports(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in moved and reports(m, name)]
    return Cell(
        name=name, chips=int(entry["chips"]),
        config=_json(os.path.join(here, "configs", entry["config"] + ".json")),
        mix=_json(os.path.join(here, "traffic", entry["traffic"] + ".json")),
        workload=_json(os.path.join(here, "workloads", name + ".json")),
        end_to_end=e2e, per_layer=per_layer)


def load_metric(name: str, here: str = HERE) -> Callable[[dict], object]:
    """The ``read(record)`` function of ``metrics/<name>.py``."""
    path = os.path.join(here, "metrics", name + ".py")
    mod_name = "serve_bench_metric_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(device_kind: str, here: str = HERE) -> dict:
    table = _json(os.path.join(here, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(known: {sorted(table)}); add its published peaks")
    return table[device_kind]
