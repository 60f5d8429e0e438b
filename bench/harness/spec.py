"""`BENCHMARK.json` and the files it names, found by name.

A cell (an entry of ``workloads``) is resolved to four files:

* ``configs/<config>.json`` — the model configuration as it is run;
* ``traffic/<traffic>.json`` — the traffic mix, read by the general
  generator of its ``kind`` (`harness.<kind>`);
* ``cells/<cell>.json`` — what is this cell's own: its sizes that
  depend on the configuration (such as the message-store samples) and
  the limits of its correctness comparison;
* ``metrics/<metric>.py`` — one reader per per-layer metric.

Adding a configuration, a traffic mix, a cell or a metric is adding
files; nothing here changes for it.
"""
from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class SpecError(ValueError):
    """A file the spec names is missing or malformed."""


def _load_json(path: str):
    if not os.path.isfile(path):
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


@dataclass(frozen=True)
class Cell:
    """One workload of the spec with everything it names resolved."""
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    own: dict                 # cells/<name>.json
    end_to_end: tuple         # metric entries this cell reports (trace 0)
    per_layer: tuple          # metric entries this cell reports (trace 1)


def load_spec(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(spec: dict, cell_name: str, bench: str = BENCH) -> Cell:
    """The cell ``cell_name`` with its configuration, traffic, own file
    and metric lists; `SpecError` when any is missing."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell_name not in cells:
        raise SpecError(f"unknown workload {cell_name!r}; the spec has "
                        f"{sorted(cells)}")
    w = cells[cell_name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"{cell_name}: unknown config {w['config']!r}")
    cfg_entry = configs[w["config"]]
    config = _load_json(os.path.join(os.path.dirname(bench),
                                     cfg_entry["file"]))
    traffic = _load_json(os.path.join(bench, "traffic",
                                      w["traffic"] + ".json"))
    own = _load_json(os.path.join(bench, "cells", cell_name + ".json"))
    e2e = tuple(m for m in spec["end_to_end"] if _applies(m, cell_name))
    per_layer = tuple(m for m in spec["per_layer"]
                      if _applies(m, cell_name))
    for m in per_layer:
        metric_path(m["name"], bench)
    return Cell(name=cell_name, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic, own=own,
                end_to_end=e2e, per_layer=per_layer)


def metric_path(name: str, bench: str = BENCH) -> str:
    path = os.path.join(bench, "metrics", name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"no reader for per-layer metric {name!r} "
                        f"(expected {os.path.relpath(path, ROOT)})")
    return path


def check_names(spec: dict) -> list:
    """Every name and unit the spec holds that breaks the allowed
    characters, as readable strings (empty when all hold)."""
    bad = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in spec[group]:
            if not NAME_RE.match(entry["name"]):
                bad.append(f"{group}: name {entry['name']!r}")
            if "unit" in entry and not UNIT_RE.match(entry["unit"]):
                bad.append(f"{group}: unit {entry['unit']!r}")
    for w in spec["workloads"]:
        for key in ("config", "traffic"):
            if not NAME_RE.match(w[key]):
                bad.append(f"workloads: {key} {w[key]!r}")
    for c in spec["configs"]:
        for key in c["reduced"]:
            if not NAME_RE.match(key):
                bad.append(f"configs: reduced key {key!r}")
    return bad
