"""Find everything a cell needs by name, from files.

``BENCHMARK.json`` at the checkout root names the cells.  A cell's
configuration is the file its ``configs`` entry names, its traffic mix
is ``bench/traffic/<traffic>.json``, the mix's ``kind`` is run by
``bench/drivers/<kind>.py`` (a module whose ``drive(run, clock)`` sets
up, measures and checks), and each metric, end-to-end or per-layer, is
read by ``bench/metrics/<metric>.py``, a module whose ``read(run)``
returns a number or ``None`` when it finds nothing to read.  Adding a
cell, a mix, a kind of traffic or a metric is adding such files and
entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(path: str):
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def applies(metric: dict, cell: str) -> bool:
    """Whether a metric entry is reported in ``cell``."""
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    root: str
    name: str
    chips: int
    config_name: str
    config_path: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list
    per_layer: list


def cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``root``'s ``BENCHMARK.json`` with its
    configuration, traffic mix and the metric entries it reports."""
    spec = benchmark(root)
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(there are {sorted(work)})")
    w = work[name]
    (conf,) = [c for c in spec["configs"] if c["name"] == w["config"]]
    return Cell(
        root=root, name=name, chips=w["chips"],
        config_name=conf["name"],
        config_path=os.path.join(root, conf["file"]),
        config=_json(os.path.join(root, conf["file"])),
        traffic_name=w["traffic"],
        traffic=_json(os.path.join(root, "bench", "traffic",
                                   w["traffic"] + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if applies(m, name)],
    )


def _module(root: str, folder: str, name: str):
    path = os.path.join(root, "bench", folder, name + ".py")
    if not os.path.exists(path):
        raise KeyError(f"no bench/{folder}/{name}.py under {root}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, root: str = ROOT):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    return _module(root, "metrics", metric).read


def driver(kind: str, root: str = ROOT):
    """The ``drive`` function of ``bench/drivers/<kind>.py``."""
    return _module(root, "drivers", kind).drive
