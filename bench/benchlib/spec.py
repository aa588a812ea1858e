"""``BENCHMARK.json`` and the files it names.

A cell names a configuration and a traffic mix; each is a file found by
its name (``configs/<config>.json``, ``traffic/<traffic>.json``), and each
per-layer metric is a reader ``metrics/<name>.py``.  A configuration names
its own module under ``reference`` (a path from the repository's root):
its plain reference, and everything that follows its layer layout, the
weights, the program's fields and the costs.  Adding a cell, a
configuration, a mix or a metric is adding files and entries: nothing
here changes.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
import re
from typing import Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is malformed or missing."""


def check_name(name: str, what: str = "name") -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(f"bad {what} {name!r}: 1-64 of A-Z a-z 0-9 _ . -, "
                        f"not starting with . or -")
    return name


def check_unit(unit: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise SpecError(f"bad unit {unit!r}: 1-16 of A-Z a-z 0-9 _ / % . -")
    return unit


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    moves: Optional[str] = None          # per-layer only
    layer: Optional[str] = None
    workloads: Optional[List[str]] = None


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with everything it names loaded."""
    name: str
    chips: int
    config_name: str
    config: Dict
    traffic_name: str
    traffic: Dict
    end_to_end: List[Metric]
    per_layer: List[Metric]
    readers: Dict[str, Callable]


def _load_json(path: pathlib.Path, what: str) -> Dict:
    if not path.is_file():
        raise SpecError(f"{what} file {path} not found")
    with open(path) as f:
        return json.load(f)


def _exec(path: pathlib.Path, prefix: str, what: str):
    if not path.is_file():
        raise SpecError(f"{what} {path} not found")
    spec = importlib.util.spec_from_file_location(
        prefix + re.sub(r"\W", "_", path.stem), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(path: pathlib.Path) -> Callable:
    """A per-layer metric's reader: ``read(run) -> float | None``."""
    mod = _exec(path, "bench_metric_", "metric reader")
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} defines no read(run)")
    return mod.read


def load_reference(c: dict):
    """The configuration's own module: the file its ``reference`` names,
    relative to the repository's root, loaded once a process."""
    return _module(ROOT / c["reference"])


@functools.lru_cache(maxsize=None)
def _module(path: pathlib.Path):
    return _exec(path, "bench_ref_", "configuration module")


def _metric(entry: Dict, per_layer: bool) -> Metric:
    m = Metric(name=check_name(entry["name"], "metric name"),
               unit=check_unit(entry["unit"]), better=entry["better"],
               source=entry["source"], moves=entry.get("moves"),
               layer=entry.get("layer"), workloads=entry.get("workloads"))
    if m.better not in ("lower", "higher"):
        raise SpecError(f"{m.name}: better must be lower or higher")
    if per_layer and not m.moves:
        raise SpecError(f"{m.name}: a per-layer metric names what it moves")
    return m


def _reports(m: Metric, cell: str, e2e_names: List[str]) -> bool:
    if m.workloads is not None:
        return cell in m.workloads
    # without a list, a per-layer metric goes with every cell that reports
    # the end-to-end metric it moves
    return m.moves is None or m.moves in e2e_names


def load_cell(name: str, bench_dir: pathlib.Path = BENCH_DIR,
              spec_path: Optional[pathlib.Path] = None) -> Cell:
    """The cell ``name`` of ``spec_path`` (default: BENCHMARK.json at the
    root above ``bench_dir``), with its configuration, traffic and metric
    readers loaded from the files under ``bench_dir``."""
    spec_path = spec_path or bench_dir.parent / "BENCHMARK.json"
    spec = _load_json(spec_path, "benchmark")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}.get(w["config"])
    if cfg_entry is None:
        raise SpecError(f"{name}: no configuration {w['config']!r}")
    config = _load_json(bench_dir.parent / cfg_entry["file"],
                        "configuration")
    traffic = _load_json(
        bench_dir / "traffic" / f"{check_name(w['traffic'], 'traffic')}.json",
        "traffic")
    e2e = [_metric(e, False) for e in spec["end_to_end"]]
    e2e = [m for m in e2e if _reports(m, name, [])]
    e2e_names = [m.name for m in e2e]
    layer = [_metric(e, True) for e in spec["per_layer"]]
    layer = [m for m in layer if _reports(m, name, e2e_names)]
    readers = {m.name: load_reader(bench_dir / "metrics" / f"{m.name}.py")
               for m in layer}
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=layer, readers=readers)
