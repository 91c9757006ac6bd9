"""``BENCHMARK.json`` and the files it names, found by name.

A cell (``workloads`` entry) names a configuration and a traffic mix:

- configuration ``<c>``: the file its ``configs`` entry gives;
- traffic ``<t>``: ``benchmark/traffic/<t>.json``;
- metric ``<m>``: ``benchmark/metrics/<m>.py``, a module with ``NAME``,
  ``UNIT`` and ``read(run)``.

A later cell, configuration or metric is a new entry and new files; no
existing file changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list              # metric entries this cell reports
    per_layer: list


def load(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def traffic_path(root: str, traffic: str) -> str:
    return os.path.join(root, "benchmark", "traffic", f"{traffic}.json")


def metric_path(root: str, metric: str) -> str:
    return os.path.join(root, "benchmark", "metrics", f"{metric}.py")


def cell(bench: dict, root: str, name: str) -> Cell:
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(wl)}")
    w = wl[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    with open(traffic_path(root, w["traffic"])) as f:
        traffic = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def metric_module(root: str, name: str):
    path = metric_path(root, name)
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if mod.NAME != name:
        raise ValueError(f"{path} declares NAME {mod.NAME!r}")
    return mod
