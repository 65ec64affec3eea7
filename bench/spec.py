"""What one run of the benchmark is: the cell, found by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix and
lists the metrics. Everything that belongs to one of them lives in a file
of its own that is found by name, so a new cell, configuration, traffic mix
or metric is new files and new entries, never an edit:

- ``configs/<config>.json``: the configuration as it is run;
- ``references/<engine>.py``: the plain reference of the configuration's
  engine semantics (a class ``Reference``);
- ``traffic/<traffic>.json``: the traffic mix's parameters, read by the one
  general generator in ``load.py``;
- ``metrics/<metric>.py``: the reader of one metric, a function
  ``read(run) -> float | None``.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list = field(default_factory=list)   # BENCHMARK.json entries
    per_layer: list = field(default_factory=list)
    bench_dir: Path = BENCH_DIR

    def metrics(self, trace: bool) -> list:
        """The metric entries a run reports: per-layer with ``--trace 1``,
        end-to-end otherwise."""
        return self.per_layer if trace else self.end_to_end


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a file by path (names may hold '-' and '.')."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace("-", "_").replace(".", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(entry: dict, cell: str, e2e_names: set) -> bool:
    """Whether a metric entry is reported in ``cell``: the cells its
    ``workloads`` lists, or else every cell that reports the end-to-end
    metric it ``moves`` (end-to-end metrics without the key: every cell)."""
    if "workloads" in entry:
        return cell in entry["workloads"]
    if "moves" in entry:
        return entry["moves"] in e2e_names
    return True


def resolve(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload`` in ``root/BENCHMARK.json``."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    bench_dir = root / spec["paths"][0]
    e2e = [m for m in spec["end_to_end"] if applies(m, workload, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if applies(m, workload, names)]
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], traffic_name=w["traffic"],
                config=load_json(root / cfg_entry["file"]),
                traffic=load_json(bench_dir / "traffic"
                                  / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)


def reference_class(cell: Cell):
    engine = cell.config["engine"]
    return load_module(cell.bench_dir / "references" / f"{engine}.py",
                       engine).Reference


def metric_reader(cell: Cell, name: str):
    return load_module(cell.bench_dir / "metrics" / f"{name}.py", name).read
