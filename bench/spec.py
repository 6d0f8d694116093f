"""Find a cell's parts by name.

``BENCHMARK.json`` at the checkout's root names each cell's
configuration, traffic mix and metrics.  Each part sits in a file of
its own under this directory, found by that name alone:

* configuration ``<c>``  -> the file the ``configs`` entry names
  (``bench/configs/<c>.json``);
* its trace family ``<f>`` (the configuration's ``trace.family``)
  -> ``bench/families/<f>.py``, with ``draw`` and ``groups``
  (``bench/gen.py``);
* traffic mix ``<t>``    -> ``bench/traffic/<t>.json``, parameters
  read by the driver the file names;
* driver ``<d>``         -> ``bench/drivers/<d>.py``, with a class
  ``Driver`` (``bench/drivers/__init__.py``);
* metric ``<m>``         -> ``bench/metrics/<m>.py``, a reader with
  ``read(record) -> float | None``.

So a later change adds a cell, a configuration, a trace family, a mix,
a driver or a metric by adding files and entries, and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]
    driver: type
    family: ModuleType


def part(kind: str, name: str, root: Path = ROOT) -> ModuleType:
    """The module ``bench/<kind>/<name>.py``."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def reader(name: str, root: Path = ROOT) -> Callable:
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    return part("metrics", name, root).read


def driver(name: str, root: Path = ROOT) -> type:
    """The ``Driver`` class of ``bench/drivers/<name>.py``."""
    return part("drivers", name, root).Driver


def family(name: str, root: Path = ROOT) -> ModuleType:
    """The trace family ``bench/families/<name>.py``."""
    return part("families", name, root)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: Optional[dict] = None,
              root: Path = ROOT) -> Cell:
    bench = load_benchmark(root) if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    metrics = lambda group: [                          # noqa: E731
        Metric(m["name"], m["unit"], reader(m["name"], root))
        for m in bench[group] if _applies(m, name)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=metrics("end_to_end"),
                per_layer=metrics("per_layer"),
                driver=driver(traffic["driver"], root),
                family=family(config["trace"]["family"], root))


def all_cells(bench: Optional[dict] = None,
              root: Path = ROOT) -> Dict[str, Cell]:
    bench = load_benchmark(root) if bench is None else bench
    return {w["name"]: load_cell(w["name"], bench, root)
            for w in bench["workloads"]}
