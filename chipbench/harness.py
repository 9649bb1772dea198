"""Finds a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, cell or metric
sits in a file of its own, so a later change adds a cell by adding files:

* ``chipbench/configs/<config>.json``: the model as it is run, its source,
  what was reduced and what was assumed;
* ``chipbench/traffic/<traffic>.json``: the traffic mix, naming its driver
  (``chipbench/drivers/<driver>.py``) and the driver's parameters;
* ``chipbench/workloads/<cell>.json``: the limits of the cell's comparison
  with the reference, and the readings they were set from;
* ``chipbench/metrics/<metric>.py``: one reader per metric.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def check_name(name: str) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise ValueError(f"bad name {name!r}: 1-64 of letters, digits, '_', '.', '-'")
    return name


def check_unit(unit: str) -> str:
    if not isinstance(unit, str) or not UNIT.match(unit):
        raise ValueError(f"bad unit {unit!r}: 1-16 of letters, digits, '_', '/', '%', '.', '-'")
    return unit


def _json(kind: str, name: str, base: Path) -> dict:
    path = base / kind / f"{check_name(name)}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def load_benchmark(root: Path = ROOT) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        check_name(m["name"])
        check_unit(m["unit"])
    return bench


def load_cell(name: str, root: Path = ROOT, base: Path = HERE) -> dict:
    """The cell ``name`` with its configuration, traffic, limits and the
    metrics it reports, each as the benchmark names them."""
    bench = load_benchmark(root)
    cells = {c["name"]: c for c in bench["workloads"]}
    if check_name(name) not in cells:
        raise KeyError(f"no cell named {name!r} in BENCHMARK.json")
    return cell_files(cells[name], bench, base)


def cell_files(entry: dict, bench: dict, base: Path = HERE) -> dict:
    """A ``workloads`` entry with the files it names loaded."""
    cell = dict(entry)
    name = cell["name"]
    cell["config_file"] = _json("configs", cell["config"], base)
    cell["traffic_file"] = _json("traffic", cell["traffic"], base)
    cell["workload_file"] = _json("workloads", name, base)

    def reported(metrics):
        return [m for m in metrics if "workloads" not in m or name in m["workloads"]]

    cell["end_to_end"] = reported(bench["end_to_end"])
    cell["per_layer"] = reported(bench["per_layer"])
    return cell


def metric_reader(name: str, base: Path = HERE):
    """The ``read(record)`` function of ``chipbench/metrics/<name>.py``."""
    path = base / "metrics" / f"{check_name(name)}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def driver(name: str):
    return importlib.import_module(f"chipbench.drivers.{check_name(name)}")


def peaks(device_kind: str, base: Path = HERE) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an error."""
    table = json.loads((base / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table["devices"][device_kind]
