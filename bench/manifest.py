"""Resolve a cell of ``BENCHMARK.json`` to the files it names.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in files of its own, found by name:

- ``bench/configs/<config>.json``: the sizes as run, with the plain
  reference module ``bench/configs/<config>.py`` beside it;
- ``bench/traffic/<traffic>.json``: the mix's parameters;
- ``bench/limits/<cell>.json``: the limits of the numbers `correct`
  compares in that cell;
- ``bench/metrics/<metric>.py``: the reader of one per-layer metric.

So a later change adds a configuration, a mix or a metric by adding files
and entries, and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
MANIFEST = ROOT / "BENCHMARK.json"
LIMITS_DIR = BENCH / "limits"
METRICS_DIR = BENCH / "metrics"


def load_module(path: Path) -> ModuleType:
    """Import a file by path under a private name (metric and
    configuration files are named after their entries, dots included)."""
    if not path.exists():
        raise FileNotFoundError(path)
    name = "bench_" + "_".join(path.with_suffix("").parts[-2:]).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # BENCHMARK.json entry
    sizes: dict  # bench/configs/<config>.json
    model: ModuleType  # bench/configs/<config>.py
    traffic: str
    mix: dict  # bench/traffic/<traffic>.json
    limits: dict  # bench/limits/<cell>.json
    end_to_end: List[dict]  # the metrics this cell reports with --trace 0
    per_layer: List[dict]  # ... and with --trace 1


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(cell_name: str, manifest: Optional[Path] = None) -> Cell:
    from bench import traffic as traffic_mod

    manifest = manifest or MANIFEST
    spec = json.loads(manifest.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r} in {manifest.name}; "
                       f"have {sorted(cells)}")
    w = cells[cell_name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = configs[w["config"]]
    sizes_path = ROOT / config["file"]
    sizes = json.loads(sizes_path.read_text())
    model = load_module(sizes_path.with_suffix(".py"))
    limits_path = LIMITS_DIR / f"{cell_name}.json"
    limits = json.loads(limits_path.read_text())
    return Cell(
        name=cell_name, chips=int(w["chips"]), config=config, sizes=sizes,
        model=model, traffic=w["traffic"], mix=traffic_mod.load_mix(w["traffic"]),
        limits=limits,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, cell_name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, cell_name)],
    )


def reader(metric_name: str) -> ModuleType:
    return load_module(METRICS_DIR / f"{metric_name}.py")
