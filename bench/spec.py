"""Finds a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own, so a cell or metric is added by adding files and
entries, never by editing code:

* configuration ``<c>``: the ``file`` its ``configs`` entry names;
* traffic mix ``<t>``: ``bench/traffic/<t>.json``;
* metric ``<m>`` (end-to-end or per-layer): ``bench/metrics/<m>.py``, a
  module with ``read(run)`` returning the value, or ``None`` where the run
  holds nothing to read.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    source: str
    per_layer: bool
    read: object  # callable(run) -> float | None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    chips: int
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def load_benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(root: str, name: str):
    """``read`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    if mod_spec is None or not os.path.isfile(path):
        raise FileNotFoundError(f"metric {name!r} has no reader at {path}")
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def _metrics(root: str, entries, cell: str, per_layer: bool):
    return tuple(
        Metric(name=m["name"], unit=m["unit"], source=m["source"],
               per_layer=per_layer, read=load_reader(root, m["name"]))
        for m in entries if cell in m.get("workloads", (cell,)))


def resolve(root: str, workload: str) -> Cell:
    """The cell named ``workload``, with its configuration, traffic mix and
    the readers of the metrics it reports; ``KeyError`` for an unknown
    cell."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    return Cell(
        name=workload,
        config_name=w["config"],
        config=_load_json(os.path.join(root, configs[w["config"]]["file"])),
        traffic_name=w["traffic"],
        traffic=_load_json(os.path.join(root, "bench", "traffic",
                                        f"{w['traffic']}.json")),
        chips=int(w["chips"]),
        end_to_end=_metrics(root, bench["end_to_end"], workload, False),
        per_layer=_metrics(root, bench["per_layer"], workload, True),
    )
