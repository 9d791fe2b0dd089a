"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

Nothing here knows a configuration, a family, a mix or a metric by name:
a cell is the ``workloads`` entry, its configuration is
``configs/<config>.json``, the configuration's model family
``families/<family>.py``, its traffic ``traffic/<traffic>.json``, and each
metric a reader module ``endtoend/<name>.py`` or ``metrics/<name>.py``
with a ``read(run)`` function.  Adding any of them is adding files and
entries.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Callable, Optional

from chipbench.model import family_name

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
    family: ModuleType
    traffic: dict
    end_to_end: list[Metric]
    per_layer: list[Metric]


@functools.cache
def _load(path: Path) -> ModuleType:
    """The module of one file, loaded by its path, once per path (metric
    names carry dots, so they are not importable module names).  It is
    registered under a name of its path, as a dataclass in it needs."""

    name = f"chipbench_{path.parent.name}_{hashlib.sha256(bytes(path)).hexdigest()[:12]}"
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reader(path: Path) -> Callable:
    """The ``read`` function of one metric file."""

    return _load(path.resolve()).read


def load_family(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The model family ``families/<name>.py``: a module that defines

    * ``dims(config)``: a frozen, hashable dataclass of the sizes the other
      functions need, with at least ``vocab`` and ``n_layers``;
    * ``arch_config(config, dims)``: the registry ``ArchConfig`` the engine
      builds (the one function that imports ``repro``);
    * ``param_shapes(dims)``, ``init(key, dims)`` and
      ``param_count(dims, *, embed)``: the engine's parameter tree, how
      each leaf is drawn, and its size;
    * ``hidden(params, dims, tokens, *, bits, q_chunk)`` and
      ``logits(params, h, *, bits)``: the plain reference, float32 at
      ``highest``, importing nothing of ``repro``, rounded by ``bits`` for
      the control;
    * ``step_gemms(dims, rows)``, ``attn_roofline_s(dims, contexts,
      peak_flops, peak_bw)``, ``token_flops(dims, context)`` and
      ``prompt_flops(dims, p)``: the work, counted from the shapes.

    One module object per file, so that it can be a static argument of a
    jitted function."""

    return _load((bench_dir / "families" / f"{name}.py").resolve())


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

    return Cell(name=name, chips=int(w["chips"]), config=config,
                family=load_family(family_name(config), bench_dir), traffic=traffic,
                end_to_end=metrics(bench["end_to_end"], "endtoend"),
                per_layer=metrics(bench["per_layer"], "metrics"))
