"""Find every part of a cell by its name in ``BENCHMARK.json``.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix. Each lives in a file of its own:

* configuration: the ``file`` that ``BENCHMARK.json`` gives for it
  (``chipbench/configs/<name>.json``), beside the plain reference module
  that its ``reference`` key names;
* traffic mix: ``chipbench/traffic/<traffic>.json``;
* cell settings (correctness sample and limit): ``chipbench/cells/<cell>.json``;
* metric: ``chipbench/metrics/<metric>.py``, a module with ``read(ctx)``.

A later cell, configuration, mix or metric is added as files and entries;
no code here names one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parent


def load_benchmark(path: Path = CHECKOUT / "BENCHMARK.json") -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _read_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def workload(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for c in bench["configs"]:
        if c["name"] == name:
            return _read_json(CHECKOUT / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> Dict[str, Any]:
    return _read_json(BENCH_DIR / "traffic" / f"{name}.json")


def cell(name: str) -> Dict[str, Any]:
    return _read_json(BENCH_DIR / "cells" / f"{name}.json")


def metrics_of(bench: Dict[str, Any], cell_name: str,
               kind: str) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    whose ``workloads`` list names it, or that have no such list."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric_name: str):
    """The ``read(ctx)`` function of ``chipbench/metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{metric_name}.py"
    return load_module(path, "chipbench_metric_" + metric_name.replace(
        ".", "_").replace("-", "_")).read


def reference(cfg: Dict[str, Any]):
    """The plain reference module a configuration names."""
    name = cfg["reference"]
    return load_module(BENCH_DIR / "configs" / f"{name}.py",
                       "chipbench_ref_" + name.replace("-", "_"))
