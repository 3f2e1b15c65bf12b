"""Find everything a cell needs by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration, whose ``file``
is the configuration as it is run, and a traffic mix, found as
``bench/traffic/<traffic>.json``.  A per-layer metric ``<name>`` is read by
``bench/metrics/<name>.py``, whose ``read(run)`` returns a number or
``None`` when the run holds nothing to read.  Adding a cell, a mix or a
metric adds files and entries; no code here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: Path
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(path: Path, workload: str) -> Cell:
    """The cell ``workload`` of the benchmark file at ``path``; file
    names in it are relative to the file's directory."""
    root = Path(path).resolve().parent
    bench = json.loads(Path(path).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfgs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((root / cfgs[w["config"]]["file"]).read_text())
    return Cell(
        name=workload, chips=int(w["chips"]), config=cfg,
        traffic=root / "bench" / "traffic" / f"{w['traffic']}.json",
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _applies(m, workload)),
        per_layer=tuple(m for m in bench["per_layer"]
                        if _applies(m, workload)))


def reader(name: str, metrics_dir: Path = BENCH / "metrics"):
    """The ``read`` function of the per-layer metric ``name``."""
    path = metrics_dir / f"{name}.py"
    mod_name = "bench_metric_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
