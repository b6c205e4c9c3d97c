"""Finds everything a cell needs by name, from files alone.

  BENCHMARK.json (checkout root)   cells, metrics, which cell reports what
  bench/configs/<config>.json      a deployment: shapes, K, grid, chain route
  bench/traffic/<traffic>.json     a traffic mix: runner kind + parameters
  bench/limits/<cell>.json         the limit of each number ``correct`` compares
  bench/runners/<runner>.py        ``run(ctx)`` for a runner kind
  bench/metrics/<metric>.py        ``read(ctx)`` for a per-layer metric

A later cell, configuration, traffic mix or metric is added by adding
files (and its entry in BENCHMARK.json); nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def cell(name: str, bench: dict) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(name: str, bench_dir: Path = BENCH) -> dict:
    return _json(bench_dir / "configs" / f"{name}.json")


def traffic(name: str, bench_dir: Path = BENCH) -> dict:
    return _json(bench_dir / "traffic" / f"{name}.json")


def limits(cell_name: str, bench_dir: Path = BENCH) -> dict:
    return _json(bench_dir / "limits" / f"{cell_name}.json")


def _load(path: Path, attr: str):
    if not path.is_file():
        raise FileNotFoundError(path)
    mod_name = "bench_dyn_" + "_".join(path.relative_to(path.parents[1])
                                       .with_suffix("").parts).replace(
                                           ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, attr)


def runner(kind: str, bench_dir: Path = BENCH):
    return _load(bench_dir / "runners" / f"{kind}.py", "run")


def metric_reader(name: str, bench_dir: Path = BENCH):
    return _load(bench_dir / "metrics" / f"{name}.py", "read")


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def end_to_end(bench: dict, cell_name: str) -> list:
    return [m for m in bench["end_to_end"] if _applies(m, cell_name)]


def per_layer(bench: dict, cell_name: str) -> list:
    return [m for m in bench["per_layer"] if _applies(m, cell_name)]
