"""Find a cell's files by name.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Everything
that belongs to one configuration, traffic mix, per-layer metric or cell
sits in a file of its own under ``<root>/bench``:

* ``configs/<config>.json``   sizes of the model as run (the path is the
                              configuration's ``file`` in BENCHMARK.json)
* ``reference/<name>.py``     the plain reference the config names
* ``traffic/<traffic>.json``  the traffic mix and the pool it is served by
* ``limits/<workload>.json``  the limits of the numbers ``correct`` compares
* ``metrics/<metric>.py``     the reader of one per-layer metric (or of
                              the metric it is split from, see
                              ``reader_path``)

Adding a cell, a configuration, a mix or a metric adds files and entries;
no code here names any of them.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

REQUIRED_CONFIG_KEYS = ("name", "source", "reduced", "deployment",
                        "reference", "dtype")
REQUIRED_TRAFFIC_KEYS = ("loop", "prompt", "output", "pool")


class CellError(ValueError):
    """A cell, or a file it names, is missing or malformed."""


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    moves: Optional[str] = None
    bound: Optional[float] = None
    reader: Optional[Callable] = None     # per-layer metrics only


@dataclass
class Cell:
    root: Path
    workload: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict[str, float]
    reference: object                     # the config's reference module
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)


def load_json(path: Path) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise CellError(f"missing file {path}") from None


def load_module(path: Path, name: str):
    if not path.is_file():
        raise CellError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_config(cfg: Dict, path: Path) -> None:
    missing = [k for k in REQUIRED_CONFIG_KEYS if k not in cfg]
    if missing:
        raise CellError(f"{path}: config file lacks {missing}; every "
                        f"configuration states its source, what was "
                        f"reduced from it and the deployment it stands "
                        f"for")
    if not isinstance(cfg["reduced"], list):
        raise CellError(f"{path}: 'reduced' must be a list of keys")
    for key in cfg["reduced"]:
        if key not in cfg:
            raise CellError(f"{path}: reduced key {key!r} is not in the "
                            f"file")


def reader_path(root: Path, name: str) -> Path:
    """``metrics/<name>.py``; a metric split by the cells that report
    it (``step.decode_ms.chat``) falls back to the reader of the name
    without its last part (``step.decode_ms``)."""
    base = name
    while True:
        path = root / "bench" / "metrics" / f"{base}.py"
        if path.is_file() or "." not in base:
            return path
        base = base.rsplit(".", 1)[0]


def _applies(entry: Dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def load_cell(root: Path, workload: str) -> Cell:
    """Everything one run of ``workload`` needs, found by name."""
    root = Path(root)
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json; "
                        f"known: {sorted(cells)}")
    w = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in confs:
        raise CellError(f"workload {workload!r} names unknown config "
                        f"{w['config']!r}")
    cpath = root / confs[w["config"]]["file"]
    config = load_json(cpath)
    check_config(config, cpath)
    tpath = root / "bench" / "traffic" / f"{w['traffic']}.json"
    traffic = load_json(tpath)
    missing = [k for k in REQUIRED_TRAFFIC_KEYS if k not in traffic]
    if missing:
        raise CellError(f"{tpath}: traffic file lacks {missing}")
    limits = load_json(root / "bench" / "limits" / f"{workload}.json")
    reference = load_module(
        root / "bench" / "reference" / f"{config['reference']}.py",
        config["reference"])
    cell = Cell(root, workload, int(w["chips"]), config, traffic,
                limits, reference)
    for m in bench["end_to_end"]:
        if _applies(m, workload):
            cell.end_to_end.append(Metric(m["name"], m["unit"], m["better"],
                                          m["source"], bound=m["bound"]))
    for m in bench["per_layer"]:
        if _applies(m, workload):
            mod = load_module(reader_path(root, m["name"]), m["name"])
            cell.per_layer.append(Metric(m["name"], m["unit"], m["better"],
                                         m["source"], moves=m["moves"],
                                         reader=mod.read))
    return cell
