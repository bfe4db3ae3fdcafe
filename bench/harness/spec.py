"""Cells, configurations, traffic mixes, limits and metric readers, found
by the names ``BENCHMARK.json`` gives them.

* ``bench/configs/<config>.json``: the deployment (sizes, knobs, source);
* ``bench/traffic/<traffic>.json``: the mix, read by the runner its
  ``kind`` names;
* ``bench/limits/<cell>.json``: the limit of each number the correctness
  check compares;
* ``bench/metrics/<metric>.py``: a reader ``read(rec) -> float | None``.

Adding a cell, a configuration, a mix or a per-layer metric adds files
and entries; no file that exists changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: Dict[str, dict]
    end_to_end: List[dict]     # the e2e metrics this cell reports
    per_layer: List[dict]      # the per-layer metrics this cell reports


def load_cell(name: str, benchmark: str = None) -> Cell:
    spec = _json(benchmark or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    # a per-layer metric without a workloads list goes wherever the
    # end-to-end metric it moves is reported
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_json(os.path.join(BENCH, "configs", w["config"] + ".json")),
        traffic=_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json")),
        limits=_json(os.path.join(BENCH, "limits", name + ".json")),
        end_to_end=e2e, per_layer=per_layer)


def reader(metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(BENCH, "metrics", metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
