"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

Nothing here knows a configuration, a mix or a metric by name: a cell is
the ``workloads`` entry, its configuration is ``configs/<config>.json``, its
traffic ``traffic/<traffic>.json``, and each metric a reader module
``endtoend/<name>.py`` or ``metrics/<name>.py`` with a ``read(run)``
function.  Adding any of them is adding files and entries.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Optional

BENCH_DIR = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    read: Callable  # read(run) -> float | None
    moves: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[Metric]
    per_layer: list[Metric]


def load_reader(path: Path) -> Callable:
    """The ``read`` function of one metric file, loaded by its path (metric
    names carry dots, so they are not importable module names)."""

    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{path.stem}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _reports(entry: dict, cell: str, e2e_cells: dict[str, set]) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    moved = entry.get("moves")
    return moved is None or cell in e2e_cells.get(moved, ())


def load_cell(name: str, root: Path, bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files loaded
    from ``bench_dir``."""

    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json").read_text())

    all_cells = set(cells)
    e2e_cells = {m["name"]: set(m.get("workloads", all_cells)) for m in bench["end_to_end"]}

    def metrics(entries, sub):
        return [
            Metric(name=m["name"], unit=m["unit"], better=m["better"], source=m["source"],
                   moves=m.get("moves"),
                   read=load_reader(bench_dir / sub / f"{m['name']}.py"))
            for m in entries if _reports(m, name, e2e_cells)
        ]

    return Cell(name=name, chips=int(w["chips"]), config=config, traffic=traffic,
                end_to_end=metrics(bench["end_to_end"], "endtoend"),
                per_layer=metrics(bench["per_layer"], "metrics"))
