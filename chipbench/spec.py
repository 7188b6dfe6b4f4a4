"""Find a cell's parts by name: ``BENCHMARK.json`` at the checkout root
names the cells and metrics; each configuration, traffic mix, limit set
and per-layer metric reader is a file of its own under ``chipbench/``."""
from __future__ import annotations

import importlib.util
import json
import os
from typing import NamedTuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list      # BENCHMARK.json metric entries this cell reports
    per_layer: list


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names


def cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files loaded."""
    bench = benchmark(root)
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; one of "
                       f"{[w['name'] for w in bench['workloads']]}")
    w = found[0]
    conf = [c for c in bench["configs"] if c["name"] == w["config"]][0]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]),
                config=_json(os.path.join(root, conf["file"])),
                traffic=_json(os.path.join(HERE, "traffic",
                                           f"{w['traffic']}.json")),
                limits=_json(os.path.join(HERE, "limits", f"{name}.json")),
                end_to_end=e2e, per_layer=per_layer)


def reader(metric: str):
    """The ``read(run)`` function of ``chipbench/metrics/<metric>.py``."""
    path = os.path.join(HERE, "metrics", f"{metric}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def layers() -> dict:
    return _json(os.path.join(HERE, "layers.json"))


def peaks(device_kind: str) -> dict:
    table = _json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "chipbench/peaks.json")
    return table[device_kind]
