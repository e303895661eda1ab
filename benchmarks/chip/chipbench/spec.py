"""Find a cell's parts by name: the workload in ``BENCHMARK.json``, its
configuration file, its traffic file and the reader of each metric it
reports.  Adding a cell, configuration, traffic mix or metric is adding
files and entries; nothing here changes."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, List

HERE = Path(__file__).resolve().parents[1]          # benchmarks/chip
ROOT = HERE.parents[1]                               # the checkout


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(workload: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(by_name)})")
    w = by_name[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name=workload, chips=w["chips"], conf=conf, traffic=traffic,
                end_to_end=e2e, per_layer=per_layer)


def reader(metric: str) -> Callable:
    """``read(run)`` of ``metrics/<metric>.py``, or, where there is no
    such file, of ``metrics/<metric up to its first '.'>.py``, the reader
    that all of a metric's per-cell variants (``device_idle.bulk``,
    ``device_idle.knee``) share: the metric's value, or None where the
    run holds nothing to read it from."""
    path = HERE / "metrics" / f"{metric}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{metric.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
