"""Finding a cell's files by name.

``BENCHMARK.json`` (at the checkout's root) names the cell, its
configuration and its traffic mix. Everything else is found by name:
``bench/cells/<cell>.json`` (the deployment: slots, cache length, prefill
settings, backlog, and the limit of the output check),
``bench/traffic/<mix>.json`` (the mix's lengths), the configuration's
``file``, the family module ``bench/reference/<family>.py`` that the
configuration names by ``"reference"`` (its shapes, parameter tree, GEMM
costs and float32 reference) and ``bench/metrics/<metric>.py`` (one reader
per metric). A new cell, mix, configuration, family or metric is files of
its own plus entries in ``BENCHMARK.json``.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
from typing import Dict, List


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict  # the configuration file
    traffic: Dict  # the mix file
    deploy: Dict  # the cell file
    end_to_end: List[Dict]  # metric entries this cell reports, trace 0
    per_layer: List[Dict]  # metric entries this cell reports, trace 1
    bench_dir: str

    def reader(self, metric: str):
        return _module(self.bench_dir, "metrics", metric).read

    @functools.cached_property
    def family(self):
        """The configuration's family module, loaded on first use (it
        imports JAX, which reads its settings from the environment then)."""
        return _module(self.bench_dir, "reference", self.config["reference"])


def _module(bench_dir: str, kind: str, name: str):
    path = os.path.join(bench_dir, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _reports(entry: Dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(root: str, name: str) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {sorted(wl)}")
    w = wl[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    bench_dir = os.path.join(root, "bench")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=_json(os.path.join(root, conf["file"])),
        traffic=_json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")),
        deploy=_json(os.path.join(bench_dir, "cells", name + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        bench_dir=bench_dir,
    )
