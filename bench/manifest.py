"""Find a cell and everything it names, by name alone.

``BENCHMARK.json`` lists the cells, configurations and metrics. A cell's
configuration is the JSON file its ``configs`` entry names; its traffic mix
is ``<bench>/traffic/<traffic>.json``; every metric, end-to-end or per-layer,
is read by ``<bench>/metrics/<metric>.py``. Adding a cell, a mix, a
configuration or a metric adds files and entries and edits nothing.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict        # the configuration file's contents
    traffic: dict       # the mix file's contents, plus its "name"
    metrics: list       # the BENCHMARK.json entries this run reports


def load(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def reported(man: dict, cell: str, trace: bool) -> list:
    """The metrics a run of ``cell`` reports: its end-to-end metrics with
    ``--trace 0``, its per-layer metrics with ``--trace 1``. A metric
    without ``workloads`` is reported wherever it can be: an end-to-end
    one in every cell, a per-layer one in every cell that reports the
    metric it ``moves``."""
    e2e = [m for m in man["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in man["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def cell(name: str, trace: bool = False, root: Path = ROOT) -> Cell:
    man = load(root)
    w = _by_name(man["workloads"], name, "workload")
    cfg_entry = _by_name(man["configs"], w["config"], "config")
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    bench = root / man["paths"][0]
    with open(bench / "traffic" / f"{w['traffic']}.json") as f:
        traffic = dict(json.load(f), name=w["traffic"])
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, metrics=reported(man, name, trace))


def reader(metric: str, root: Path = ROOT):
    """The ``read(run)`` function of ``<bench>/metrics/<metric>.py``."""
    path = root / load(root)["paths"][0] / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    with open(root / load(root)["paths"][0] / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} has no row in "
                       f"peaks.json; add its published peaks")
    return table[device_kind]
