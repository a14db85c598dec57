"""Discovery by name.  ``BENCHMARK.json`` names each cell's configuration
and traffic; everything else is found from those names:

* ``configs[].file`` — the configuration as it is run (JSON); its plain
  reference is the ``.py`` file beside it, with the same stem;
* ``perfbench/traffic/<traffic>.json`` — the traffic mix; its ``kind``
  names the runner ``perfbench/runners/<kind>.py``;
* ``perfbench/metrics/<metric>.py`` — one reader per metric.

A new cell, traffic mix or metric is new files and entries; no file here
changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

from harness.common import BENCH_DIR, ROOT


_LOADED: Dict[Path, ModuleType] = {}


def load_module(path: Path, name: Optional[str] = None) -> ModuleType:
    """Import a file by path (file names may hold '-' and '.'), once."""
    path = Path(path).resolve()
    if path in _LOADED:
        return _LOADED[path]
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    mod_name = name or "pb_" + "".join(
        c if c.isalnum() else "_" for c in str(path.relative_to(ROOT)))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _LOADED[path] = mod
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict            # the configuration file, as run
    reference: ModuleType   # the plain reference beside it
    traffic: Dict           # the traffic file
    end_to_end: List[Dict]  # metric entries this cell reports
    per_layer: List[Dict]

    def runner(self) -> ModuleType:
        return load_module(BENCH_DIR / "runners" / f"{self.traffic['kind']}.py")


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def metric_reader(name: str) -> ModuleType:
    return load_module(BENCH_DIR / "metrics" / f"{name}.py")


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, bench: Optional[Dict] = None) -> Cell:
    bench = bench if bench is not None else load_benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(by_name)})")
    w = by_name[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    conf_path = ROOT / conf["file"]
    with open(conf_path) as f:
        spec = json.load(f)
    with open(BENCH_DIR / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"], config=spec,
        reference=load_module(conf_path.with_suffix(".py")),
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def read_metrics(entries: List[Dict], ctx: Dict) -> Dict[str, Dict]:
    """Each metric's reader over the run's context; a reader that finds
    nothing to read returns None and the metric is left out."""
    out: Dict[str, Dict] = {}
    for m in entries:
        value = metric_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
