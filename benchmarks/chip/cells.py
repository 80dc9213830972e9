"""Find a cell's configuration, traffic mix and metric readers by name.

Everything a cell needs is named in ``BENCHMARK.json`` at the checkout's
root and lives in files of its own under this directory:

- ``configs/<config>.json``: the deployment (sizes, data, program settings,
  correctness limits), as the workload's ``config`` entry names it;
- ``traffic/<traffic>.json``: the traffic mix, whose ``kind`` names its
  driver;
- ``kinds/<kind>.py``: one driver per traffic kind, with a ``drive(run)``
  that runs the cell once and returns a ``drivers.Outcome``; what the
  kinds share is in ``drivers.py``;
- ``metrics/<metric>.py``: one reader per per-layer metric, with a
  ``read(ctx)`` that returns the number or None when there is nothing to
  read.

A new cell, configuration, mix, kind or metric is therefore new files and
new entries in ``BENCHMARK.json``; no file here needs an edit.
"""
from __future__ import annotations

import copy
import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class CellError(Exception):
    """A cell, file or entry named in ``BENCHMARK.json`` is missing."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list
    readers: dict           # per-layer metric name -> read(ctx)

    @property
    def metric_units(self) -> dict:
        return {m["name"]: m["unit"] for m in self.end_to_end + self.per_layer}


def _read_json(path: str, what: str) -> dict:
    if not os.path.exists(path):
        raise CellError(f"{what} not found: {path}")
    with open(path) as f:
        return json.load(f)


def _merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def _load_module(path: str, prefix: str, name: str):
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    """``metrics/<name>.py``'s ``read``; a name may hold dots."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    if not os.path.exists(path):
        raise CellError(f"reader of per-layer metric {name!r} not found: "
                        f"{path}")
    return _load_module(path, "chipbench_metric_", name).read


def load_driver(kind: str):
    """``kinds/<kind>.py``'s ``drive``, for a traffic mix's ``kind``."""
    path = os.path.join(HERE, "kinds", f"{kind}.py")
    if not os.path.exists(path):
        raise CellError(f"driver of traffic kind {kind!r} not found: "
                        f"{path}")
    return _load_module(path, "chipbench_kind_", kind).drive


def load_benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"), "BENCHMARK.json")


def load_cell(bench: dict, name: str, *, rehearse: bool = False,
              root: str = ROOT) -> Cell:
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise CellError(f"no workload named {name!r} in BENCHMARK.json "
                        f"(have {sorted(work)})")
    w = work[name]
    confs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in confs:
        raise CellError(f"workload {name!r} names configuration "
                        f"{w['config']!r}, which BENCHMARK.json lacks")
    config = _read_json(os.path.join(root, confs[w["config"]]["file"]),
                        f"configuration {w['config']!r}")
    traffic = _read_json(os.path.join(HERE, "traffic",
                                      f"{w['traffic']}.json"),
                         f"traffic mix {w['traffic']!r}")
    if rehearse:
        config = _merge(config, config.get("rehearsal", {}))
        traffic = _merge(traffic, traffic.get("rehearsal", {}))
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    readers = {m["name"]: load_reader(m["name"]) for m in layer}
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer,
                readers=readers)
