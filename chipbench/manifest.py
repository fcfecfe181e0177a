"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell is ``workloads/<cell>.json`` (the program's path and the limits of
the comparison), the configuration file that its ``BENCHMARK.json`` entry
names, and ``traffic/<traffic>.json`` (the batches). Each metric is read
by ``metrics/<metric>.py``. Adding a cell, a configuration, a traffic mix
or a metric is adding those files and entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    model: dict            # the configuration file's "model" block
    config: dict           # the whole configuration file
    traffic: dict
    workload: dict         # workloads/<cell>.json
    end_to_end: tuple      # BENCHMARK.json metric entries this cell reports
    per_layer: tuple


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: Path = ROOT) -> dict:
    return _read_json(Path(root) / "BENCHMARK.json")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, *, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    root, bench_dir = Path(root), Path(bench_dir)
    manifest = load_manifest(root)
    entry = {w["name"]: w for w in manifest["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    config_entry = {c["name"]: c for c in manifest["configs"]}[
        entry["config"]]
    config = _read_json(root / config_entry["file"])
    return Cell(
        name=name, chips=int(entry["chips"]), model=config["model"],
        config=config,
        traffic=_read_json(bench_dir / "traffic" / f"{entry['traffic']}.json"),
        workload=_read_json(bench_dir / "workloads" / f"{name}.json"),
        end_to_end=tuple(m for m in manifest["end_to_end"]
                         if _reports(m, name)),
        per_layer=tuple(m for m in manifest["per_layer"]
                        if _reports(m, name)))


def metric_reader(name: str, bench_dir: Path = BENCH_DIR
                  ) -> Callable[[Any], float | None]:
    """``read(run)`` of ``metrics/<name>.py``."""
    path = Path(bench_dir) / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
