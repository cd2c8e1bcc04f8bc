"""A cell, found by name: its entry in BENCHMARK.json, its configuration's
file, its traffic's file, the driver that traffic names and the readers of
its metrics.

Nothing here knows a cell, a configuration, a traffic mix, a driver or a
metric by name.  A later PR adds one by adding its file
(``configs/<name>.json``, ``traffic/<name>.json``, ``drivers/<name>.py``,
``metrics/<name>.py``) and its entry in BENCHMARK.json;
``tests/test_cell.py`` shows that nothing else is needed.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    root: pathlib.Path  # the checkout
    driver: str  # the traffic's "driver": drivers/<driver>.py
    config: dict  # configs/<config>.json, as run
    traffic: dict  # traffic/<traffic>.json
    end_to_end: list[dict]  # BENCHMARK.json entries that apply to this cell
    per_layer: list[dict]
    metrics_dir: pathlib.Path


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(workload: str, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    here = root / "perfbench"
    traffic = json.loads((here / "traffic" / f"{w['traffic']}.json").read_text())
    if "driver" not in traffic:
        raise KeyError(f"traffic {w['traffic']!r} names no driver")
    if not (here / "drivers" / f"{traffic['driver']}.py").is_file():
        raise KeyError(f"no driver {traffic['driver']!r} (traffic {w['traffic']!r})")
    return Cell(
        name=workload,
        chips=w["chips"],
        root=root,
        driver=traffic["driver"],
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        metrics_dir=here / "metrics",
    )


def driver(cell: Cell):
    """``drivers/<cell.driver>.py`` of the cell's checkout, as a module
    (``drivers/__init__.py`` says what it provides)."""
    path = cell.root / "perfbench" / "drivers" / f"{cell.driver}.py"
    name = f"perfbench.drivers.{cell.driver}"
    module = sys.modules.get(name)
    if module is not None and pathlib.Path(module.__file__).resolve() == path.resolve():
        return module
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module  # dataclasses look their module up by name
    spec.loader.exec_module(module)
    return module


def reader(metrics_dir: pathlib.Path, name: str):
    """``metrics/<name>.py``'s ``read(run)``: the metric's value, or None
    where the run holds nothing to read it from."""
    path = metrics_dir / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"perfbench_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_metrics(cell: Cell, entries: list[dict], run) -> dict:
    """``{name: {"value", "unit"}}`` for each metric whose reader found
    something to read."""
    out = {}
    for m in entries:
        value = reader(cell.metrics_dir, m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
