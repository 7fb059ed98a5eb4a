"""Find a cell's parts by name: ``BENCHMARK.json``, the configuration's
file, its architecture kind, the traffic mix, the cell's own file and
the per-layer readers.

Nothing here knows a particular configuration, architecture or mix: a
new configuration of a known architecture is a new file under
``configs/`` and ``cells/`` plus entries in ``BENCHMARK.json``; one of
a new architecture adds its kind under ``arch/`` and its plain
reference under ``reference/``; a new mix or per-layer metric is a file
under ``traffic/`` or ``metrics/`` plus an entry.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List, Optional

#: this directory; configuration, architecture, traffic, cell and metric
#: files live under it
HERE = Path(__file__).resolve().parent
#: the checkout's root, where ``BENCHMARK.json`` lives
ROOT = HERE.parents[1]


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: Optional[str] = None
    moves: Optional[str] = None
    workloads: Optional[List[str]] = None


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload entry with everything it refers to loaded."""
    name: str
    chips: int
    config: Dict[str, Any]            # the configuration's file
    traffic: Dict[str, Any]           # the mix's parameter file
    params: Dict[str, Any]            # the cell's own file (rate, limits)
    end_to_end: List[Metric]          # the end-to-end metrics it reports
    per_layer: List[Metric]           # the per-layer metrics it reports
    base: Path                        # directory the files were found in


def _metric(entry: Dict[str, Any]) -> Metric:
    return Metric(**{f.name: entry.get(f.name)
                     for f in dataclasses.fields(Metric)})


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a Python file by path (metric and traffic-kind files are
    named after their metric or kind, dots included)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: Metric, cell: str, e2e_names: List[str]) -> bool:
    if metric.workloads is not None:
        return cell in metric.workloads
    if metric.moves is not None:
        return metric.moves in e2e_names
    return True


def load_cell(name: str, bench: Optional[Dict[str, Any]] = None,
              base: Path = HERE, root: Path = ROOT) -> Cell:
    """Load workload ``name`` of ``bench`` (default: the root's
    ``BENCHMARK.json``); data files are looked up under ``base``."""
    if bench is None:
        bench = load_json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(entries)}")
    w = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    cfg_path = Path(cfg_entry["file"])
    if not cfg_path.is_absolute():
        cfg_path = root / cfg_path
    config = load_json(cfg_path)
    if "kind" not in config:
        raise ValueError(f"{cfg_path} names no \"kind\": the module of its "
                         f"architecture, {base / 'arch' / '<kind>.py'}")
    kind_file = _kind_file(base, config)
    if not kind_file.exists():
        raise FileNotFoundError(f"{cfg_path} names kind "
                                f"{config['kind']!r}, but there is no "
                                f"{kind_file}")
    traffic = load_json(base / "traffic" / f"{w['traffic']}.json")
    cell_file = base / "cells" / f"{name}.json"
    params = load_json(cell_file) if cell_file.exists() else {}
    e2e = [_metric(m) for m in bench["end_to_end"]
           if _reports(_metric(m), name, [])]
    e2e_names = [m.name for m in e2e]
    per_layer = [_metric(m) for m in bench["per_layer"]
                 if _reports(_metric(m), name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, params=params, end_to_end=e2e,
                per_layer=per_layer, base=base)


def _kind_file(base: Path, config: Dict[str, Any]) -> Path:
    return base / "arch" / f"{config['kind']}.py"


def arch_kind(cell: Cell):
    """The module of the cell's architecture (``arch/<kind>.py``): its
    program configuration, weights, reference and work counts."""
    kind = cell.config["kind"]
    return load_module(_kind_file(cell.base, cell.config), "arch." + kind)


def traffic_kind(cell: Cell):
    """The generator module that reads the cell's mix."""
    kind = cell.traffic["kind"]
    return load_module(cell.base / "traffic" / "kinds" / f"{kind}.py", kind)


def metric_reader(cell: Cell, metric: Metric):
    """The ``read(records)`` function of a per-layer metric."""
    mod = load_module(cell.base / "metrics" / f"{metric.name}.py",
                      metric.name)
    return mod.read
