"""Find a cell, a configuration, a traffic mix, a generator and a per-layer
metric by the name ``BENCHMARK.json`` gives. There is no list in code: a
later PR adds files and entries and edits nothing that is here.

    benchmarks/workloads/<cell>.json        parameters of one cell
    benchmarks/traffic/<mix>.json           parameters of one traffic mix; names its generator
    benchmarks/generators/<generator>.py    the general generator a mix's data file is read by
    benchmarks/configs/<config>.json|.py    sizes as run / builder, FLOPs, plain reference
    benchmarks/layer_metrics/<metric>.py    one reader: ``read(run) -> number | None``
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchmarkError(Exception):
    """The benchmark's files do not describe a runnable cell."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import one file by path under a name of its own (no package needed,
    so two trees can hold files of the same name)."""
    if not os.path.isfile(path):
        raise BenchmarkError(f"no such file: {path}")
    name = "_bench_" + os.path.relpath(path, os.path.dirname(BENCH_DIR)) \
        .replace(os.sep, "_").removesuffix(".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One entry of ``workloads`` with everything its files say."""

    name: str
    config: str
    traffic: str
    chips: int
    params: dict                 # the mix's parameters, overridden by the cell's
    generator: str
    sizes: dict                  # the configuration as it is run
    end_to_end: list = field(default_factory=list)   # metric entries reported here
    per_layer: list = field(default_factory=list)
    bench_dir: str = BENCH_DIR

    def path(self, *parts: str) -> str:
        return os.path.join(self.bench_dir, *parts)

    def config_module(self):
        return load_module(self.path("configs", self.config + ".py"))

    def generator_module(self):
        return load_module(self.path("generators", self.generator + ".py"))

    def metric_reader(self, metric: str):
        return load_module(self.path("layer_metrics", metric + ".py")).read


def _reported(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def resolve_cell(name: str, manifest_path: str | None = None,
                 bench_dir: str = BENCH_DIR) -> Cell:
    """The cell ``name`` of the manifest, with its mix, sizes and metrics."""
    manifest_path = manifest_path or os.path.join(
        os.path.dirname(bench_dir), "BENCHMARK.json")
    manifest = load_json(manifest_path)
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchmarkError(
            f"workload {name!r} is not in {manifest_path}: "
            f"{[w['name'] for w in manifest['workloads']]}")
    conf = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    repo = os.path.dirname(manifest_path)
    mix = load_json(os.path.join(bench_dir, "traffic", entry["traffic"] + ".json"))
    cell_file = os.path.join(bench_dir, "workloads", name + ".json")
    own = load_json(cell_file) if os.path.isfile(cell_file) else {}
    params = {**mix.get("params", {}), **own.get("params", {})}
    return Cell(name=name, config=entry["config"], traffic=entry["traffic"],
                chips=int(entry["chips"]), params=params,
                generator=mix["generator"],
                sizes=load_json(os.path.join(repo, conf["file"])),
                end_to_end=_reported(manifest["end_to_end"], name),
                per_layer=_reported(manifest["per_layer"], name),
                bench_dir=bench_dir)
