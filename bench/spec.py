"""The cell a run measures, read from BENCHMARK.json and the data files
it names: the configuration (`configs/<config>.json`), the traffic mix
(`traffic/<traffic>.json`) and the cell's own settings and correctness
limits (`cells/<workload>.json`)."""
from __future__ import annotations

import dataclasses
import json

from bench import BENCH, ROOT


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    settings: dict
    end_to_end: list
    per_layer: list


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def load(workload: str, root=ROOT) -> Cell:
    bench_json = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench_json["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench_json["configs"]}[w["config"]]
    config = _load_json(root / cfg_entry["file"])
    traffic = _load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    settings = _load_json(BENCH / "cells" / f"{workload}.json")
    e2e = [m for m in bench_json["end_to_end"]
           if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench_json["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, settings=settings, end_to_end=e2e,
                per_layer=per_layer)
