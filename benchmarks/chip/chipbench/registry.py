"""Find a cell's pieces by name.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric sits in a file of its own, named after it:

* ``configs/<config>.json``  -- the model, population, optimisers, precision
* ``traffic/<traffic>.json`` -- the execution plan, the cohort shape and the
  sampler, aggregation and client-step parts
* ``limits/<cell>.json``     -- the limits of the numbers ``correct`` compares
* ``models/<model>.py``      -- data generator, FLOP count, plain loss
* ``parts/<role>/<name>.py`` -- one optimiser, sampler, aggregation or
  client step, for the program and for the reference (``chipbench.parts``)
* ``metrics/<metric>.py``    -- one reader, ``read(ctx) -> float | None``

A new cell, configuration or metric is new files plus an entry in
``BENCHMARK.json``; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(kind: str, name: str) -> ModuleType:
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + re.sub(r"[^A-Za-z0-9_]", "_", f"{kind}_{name}"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def cell(name: str, bench: dict) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (have "
                   f"{[w['name'] for w in bench['workloads']]})")


def config(name: str) -> dict:
    return _json(BENCH_DIR / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return _json(BENCH_DIR / "traffic" / f"{name}.json")


def limits(cell_name: str) -> dict:
    return _json(BENCH_DIR / "limits" / f"{cell_name}.json")


def model(name: str) -> ModuleType:
    return _module("models", name)


def metric(name: str) -> ModuleType:
    return _module("metrics", name)


def part(role: str, name: str) -> ModuleType:
    return _module(f"parts/{role}", name)


def metrics_for(cell_name: str, bench: dict, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports: those
    without a ``workloads`` key, and those that list the cell."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]
