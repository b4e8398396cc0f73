"""Rehearsing the benchmark's cells on the CPU at tiny presets. The presets
are data beside this file and live in the tests only: a cell is never
measured at these sizes.

    presets/configs/<config>.json   {"sizes": overrides of the configuration's file}
    presets/cells/<cell>.json       {"params": overrides of the cell's parameters,
                                     "reads_nothing_on_cpu": [per-layer metrics]}

``reads_nothing_on_cpu`` (optional) names the cell's per-layer metrics whose
reader finds nothing to read in a CPU rehearsal and so returns nothing: a
kernel's share of its roofline where the CPU takes the XLA path, a dispatch
boundary where a loaded CPU fits one dispatch. Every other metric the cell
lists has to be in the traced rehearsal's line.

A PR that adds a cell adds these two files, and the cell is rehearsed end to
end and traced by ``test_benchmark_rehearsal.py`` without an edit here.
"""

import copy
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness.discovery import (BenchmarkError, load_json,  # noqa: E402
                                          resolve_cell)
from benchmarks.harness.main import run_cell  # noqa: E402

PRESETS = os.path.join(HERE, "presets")
SERVING_ENTRIES = os.path.join(HERE, "fixtures", "serving_cell_entries.json")

FAKE_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
              "hbm_bytes": 1e9, "source": "tests only"}

# what ``manifest_with_a_later_prs_additions`` adds
ADDED_CONFIG, ADDED_CELL = "charrnn_1x128", "charrnn_1x128_short"
ADDED_METRIC = "dispatches_in_window"
# a kernel's share of its roofline, which reads nothing where the kernel did
# not run: on the CPU, so in every rehearsal
ADDED_ROOFLINE = "made_up_scan_roofline"
ADDED_ROOFLINE_READER = '''"""Nothing where no ``made_up_scan`` kernel ran."""
from benchmarks.harness.scopes import kernel_name


def read(run):
    ran = any(kernel_name(op.name).startswith("made_up_scan")
              for dev in run.trace.devices for op in dev.ops
              if op.bucket == "pallas")
    return 50.0 if ran else None
'''


def bench_dir_of(manifest_path: str) -> str:
    """The benchmark's files lie beside the manifest that names them."""
    return os.path.join(os.path.dirname(manifest_path), "benchmarks")


def _write(path: str, content: dict) -> None:
    with open(path, "w") as f:
        json.dump(content, f)


def manifest_with_serving_cell(tmp_dir) -> str:
    """A copy of ``BENCHMARK.json`` in ``tmp_dir`` with the decode cell's
    entries added, as a later PR would add them (the benchmark itself has no
    serving cell: ``fixtures/serving_cell_entries.json`` says why). The
    benchmark's files are reached through a link beside it."""
    m = load_json(os.path.join(REPO, "BENCHMARK.json"))
    extra = load_json(SERVING_ENTRIES)
    for section in ("workloads", "end_to_end", "per_layer"):
        m[section] = m[section] + extra[section]
    link = os.path.join(tmp_dir, "benchmarks")
    if not os.path.exists(link):
        os.symlink(os.path.join(REPO, "benchmarks"), link)
    path = os.path.join(tmp_dir, "BENCHMARK.json")
    _write(path, m)
    return path


def manifest_with_a_later_prs_additions(tmp_dir) -> str:
    """What a PR that brings a configuration leaves, under ``tmp_dir``: a
    copy of ``benchmarks/`` and of ``presets/`` with files added and none
    edited (a configuration with its module, a traffic mix, a cell, a
    per-layer metric's reader, the two presets), and the manifest with a
    configuration, a cell and a per-layer entry appended and the cell's name
    appended to the ``workloads`` of the metrics it reports."""
    tmp_dir = str(tmp_dir)
    path = os.path.join(tmp_dir, "BENCHMARK.json")
    if os.path.isfile(path):    # built by an earlier call of the same test
        return path
    bench = os.path.join(tmp_dir, "benchmarks")
    shutil.copytree(os.path.join(REPO, "benchmarks"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(PRESETS, os.path.join(tmp_dir, "presets"))
    configs = os.path.join(bench, "configs")
    shutil.copy(os.path.join(configs, "charrnn_2x512.py"),
                os.path.join(configs, ADDED_CONFIG + ".py"))
    _write(os.path.join(configs, ADDED_CONFIG + ".json"), dict(
        load_json(os.path.join(configs, "charrnn_2x512.json")),
        name=ADDED_CONFIG, rnn_size=128, num_layers=1))
    _write(os.path.join(bench, "traffic", "train_staged_short.json"), {
        "generator": "staged_training",
        "params": {"wrapper": "none", "first_loss_rtol": 0.25,
                   "reference_rtol": 0.03, "twin_rtol": [0.03],
                   "trace_seconds": 3, "batch_per_chip": 16, "seq_len": 32,
                   "slots": 8}})
    _write(os.path.join(bench, "workloads", ADDED_CELL + ".json"), {
        "why": "added by a test", "params": {"steps_per_dispatch": 8}})
    with open(os.path.join(bench, "layer_metrics", ADDED_METRIC + ".py"),
              "w") as f:
        f.write("def read(run):\n    return run.result['dispatches']\n")
    with open(os.path.join(bench, "layer_metrics", ADDED_ROOFLINE + ".py"),
              "w") as f:
        f.write(ADDED_ROOFLINE_READER)
    _write(os.path.join(tmp_dir, "presets", "configs", ADDED_CONFIG + ".json"),
           {"sizes": {"rnn_size": 16, "vocab_size": 12, "classes": 12}})
    _write(os.path.join(tmp_dir, "presets", "cells", ADDED_CELL + ".json"),
           {"params": {"batch_per_chip": 2, "seq_len": 4, "slots": 2,
                       "steps_per_dispatch": 2, "trace_seconds": 1},
            "reads_nothing_on_cpu": [ADDED_ROOFLINE]})

    m = load_json(os.path.join(REPO, "BENCHMARK.json"))
    m["configs"].append({"name": ADDED_CONFIG, "source": "test",
                         "file": f"benchmarks/configs/{ADDED_CONFIG}.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": ADDED_CELL, "config": ADDED_CONFIG,
                           "traffic": "train_staged_short", "chips": 1,
                           "why": "test"})
    m["end_to_end"][0]["workloads"].append(ADDED_CELL)
    for entry in m["per_layer"]:
        if entry["name"] in ("device_idle_share", "peak_hbm_gb",
                             "train_step_mfu"):
            entry["workloads"].append(ADDED_CELL)
    m["per_layer"].append({
        "name": ADDED_METRIC, "unit": "dispatches", "better": "higher",
        "source": "program_counter",
        "layer": "entry points: fit_on_device and ParallelWrapper",
        "moves": "train_samples_per_s_per_chip", "workloads": [ADDED_CELL]})
    m["per_layer"].append({
        "name": ADDED_ROOFLINE, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "Pallas kernels",
        "moves": "train_samples_per_s_per_chip", "workloads": [ADDED_CELL]})
    _write(path, m)
    return path


def _preset(presets_dir: str, kind: str, name: str, key: str) -> dict:
    path = os.path.join(presets_dir, kind, name + ".json")
    if not os.path.isfile(path):
        raise BenchmarkError(
            f"no tiny preset for {name!r}: add {path} with "
            f'{{"{key}": {{...}}}} so that the cell is rehearsed on the CPU')
    return load_json(path)[key]


def _presets_dir(manifest_path: str) -> str:
    """The ``presets/`` beside the manifest where a copy brought its own."""
    beside = os.path.join(os.path.dirname(manifest_path), "presets")
    return beside if os.path.isdir(beside) else PRESETS


def tiny_cell(name, manifest_path=None):
    """The cell ``name`` of the manifest (the committed one, or a copy with
    its ``benchmarks/`` and perhaps its ``presets/`` beside it), at its tiny
    preset."""
    manifest_path = manifest_path or os.path.join(REPO, "BENCHMARK.json")
    presets_dir = _presets_dir(manifest_path)
    cell = copy.deepcopy(resolve_cell(name, manifest_path=manifest_path,
                                      bench_dir=bench_dir_of(manifest_path)))
    cell.sizes.update(_preset(presets_dir, "configs", cell.config, "sizes"))
    cell.params.update(_preset(presets_dir, "cells", name, "params"))
    return cell


def reads_nothing_on_cpu(name, manifest_path=None) -> set:
    """The per-layer metrics the cell's preset says a CPU rehearsal leaves
    out of its line (see the module docstring)."""
    manifest_path = manifest_path or os.path.join(REPO, "BENCHMARK.json")
    path = os.path.join(_presets_dir(manifest_path), "cells", name + ".json")
    return set(load_json(path).get("reads_nothing_on_cpu", []))


def rehearse(cell, *, trace=False, seconds=0.5, seed=5, cpu_rehearsal=True):
    """The cell's whole run on the CPU backend's first ``chips`` devices;
    returns the parsed final line. A traced run writes under a temporary
    directory of this call's own: nothing is left in the checkout, and two
    test workers that rehearse one cell share no directory."""
    import jax

    with tempfile.TemporaryDirectory(prefix="bench_trace_") as trace_root:
        line = run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                        devices=jax.devices()[:cell.chips],
                        t0=time.perf_counter(), peaks=FAKE_PEAKS,
                        cpu_rehearsal=cpu_rehearsal, trace_root=trace_root)
    return json.loads(line)
