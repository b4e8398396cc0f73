"""The harness's own arithmetic and contracts: percentiles, the final line,
the manifest against the benchmark's contract, discovery by name, the
non-TPU exit."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_presets import (ADDED_CELL, ADDED_METRIC, ADDED_ROOFLINE, REPO,
                           bench_dir_of, manifest_with_a_later_prs_additions,
                           rehearse, tiny_cell)
from benchmarks.harness import discovery, gate, main, stats

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


# ------------------------------------------------------------- percentiles
@pytest.mark.parametrize("samples,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([5, 1, 4, 2, 3], 50, 3.0),          # order does not matter
    ([1, 2, 3, 4], 50, 2.5),             # interpolates between the middle two
    ([10], 95, 10.0),
    (list(range(1, 101)), 95, 95.05),
    (list(range(1, 101)), 0, 1.0),
    (list(range(1, 101)), 100, 100.0),
])
def test_percentile(samples, q, want):
    assert stats.percentile(samples, q) == pytest.approx(want)


def test_percentile_matches_numpy_and_refuses_nothing():
    import numpy as np

    xs = np.random.default_rng(0).lognormal(size=1001)
    for q in (50, 95, 99):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1.0], 101)


def test_samples_beyond_and_spread():
    assert stats.samples_beyond(400, 95) == 20
    assert stats.samples_beyond(150, 99) == 1
    # quartiles of 1..5 are 2 and 4, the median 3
    assert stats.spread([1, 2, 3, 4, 5]) == pytest.approx(2 / 3)
    assert stats.spread([7.0, 7.0, 7.0]) == 0.0


# -------------------------------------------------------------- final line
def test_final_line_has_the_contract_keys_and_nothing_else():
    """... but ``compared``, the harness's own key, which every line ends
    in: each number ``correct`` was decided from, beside its limit."""
    line = main.final_line(
        correct=True, attempted=4, failed=0,
        metrics={"setup_s": {"value": 1.5, "unit": "s"}},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1},
        compared={"compiles_in_window": (0, 0)})
    assert "\n" not in line
    assert list(json.loads(line)) == ["correct", "attempted", "failed",
                                      "metrics", "device", "compared"]
    traced = json.loads(main.final_line(
        correct=False, attempted=1, failed=1, metrics={}, device={},
        breakdown={"device_ops": [], "idle_gaps": []},
        compared={"first_loss_off_plain_reference": (2e-4, 1e-4),
                  "compiles_in_window": (0, 0)}))
    assert list(traced) == ["correct", "attempted", "failed", "metrics",
                            "device", "breakdown", "compared"]
    assert traced["correct"] is False
    assert traced["compared"] == {
        "first_loss_off_plain_reference": {"value": 2e-4, "limit": 1e-4},
        "compiles_in_window": {"value": 0.0, "limit": 0.0}}
    with pytest.raises(TypeError):      # no line without what was compared
        main.final_line(correct=True, attempted=1, failed=0, metrics={},
                        device={})


def test_metric_values_leave_out_what_was_not_read():
    entries = [{"name": "a", "unit": "ms"}, {"name": "b", "unit": "%"}]
    assert main.metric_values(entries, {"a": 2, "b": None, "c": 1}) == {
        "a": {"value": 2.0, "unit": "ms"}}


def test_peaks_table_knows_the_v5e_and_refuses_an_unknown_device():
    row = gate.peaks_row("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        gate.peaks_row("TPU v9 imaginary")


# ---------------------------------------------------------------- manifest
def test_manifest_has_exactly_the_contract_keys_and_limits(manifest_path):
    m = discovery.load_json(manifest_path)
    root = os.path.dirname(manifest_path)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    # a full check with all 24 cells fits 43200 s
    cells = 24
    assert (2 + 14 * cells) * (m["run_seconds"] + 60) + cells * 180 + 1200 <= 43200
    assert m["command"][:2] == ["python3", "benchmarks/run.py"]
    for p in m["paths"]:
        assert os.path.isdir(os.path.join(REPO, p)) and not p.startswith("/")
    assert os.path.isdir(os.path.join(root, m["paths"][0]))
    assert 2 <= len(m["workloads"]) <= 24
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)
    assert os.path.getsize(manifest_path) <= 64 * 1024


def test_manifest_names_units_and_references(manifest_path):
    m = discovery.load_json(manifest_path)
    root = os.path.dirname(manifest_path)
    configs = {c["name"] for c in m["configs"]}
    cells = {w["name"] for w in m["workloads"]}
    e2e = {x["name"] for x in m["end_to_end"]}
    assert "setup_s" in e2e
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in m["paths"]))
        assert os.path.isfile(os.path.join(root, c["file"]))
    pairs = set()
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert configs == {w["config"] for w in m["workloads"]}
    names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(names) == len(set(names))
    for x in m["end_to_end"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert x["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= x["bound"] <= 0.1
    for x in m["per_layer"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert x["source"] in SOURCES and x["moves"] in e2e
        assert 1 <= len(x["layer"]) <= 200
    for x in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        assert set(x.get("workloads", cells)) <= cells


def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric(
        manifest_path):
    m = discovery.load_json(manifest_path)
    for w in m["workloads"]:
        cell = discovery.resolve_cell(w["name"], manifest_path=manifest_path,
                                      bench_dir=bench_dir_of(manifest_path))
        e2e = {x["name"] for x in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        # a per-layer metric is reported only where the metric it moves is
        for x in cell.per_layer:
            assert x["moves"] in e2e, (w["name"], x["name"])
            assert os.path.isfile(cell.path("layer_metrics", x["name"] + ".py"))
        assert os.path.isfile(cell.path("generators", cell.generator + ".py"))
        assert os.path.isfile(cell.path("configs", cell.config + ".py"))


def test_a_kernels_roofline_share_is_named_for_it(manifest_path):
    for x in discovery.load_json(manifest_path)["per_layer"]:
        if "roofline" in x["name"]:
            assert x["name"].endswith("_roofline") and x["unit"] == "%"


# --------------------------------------------------------------- discovery
def test_unknown_workload_is_named_in_the_error():
    with pytest.raises(discovery.BenchmarkError, match="no_such_cell"):
        discovery.resolve_cell("no_such_cell")


def test_a_cell_mix_config_and_metric_added_as_files_are_found(tmp_path):
    """A later PR adds files and manifest entries and edits nothing: the
    same harness code then runs the new cell and reads the new metric, and
    the same tests rehearse it through the two presets it added."""
    path = manifest_with_a_later_prs_additions(tmp_path)
    cell = discovery.resolve_cell(ADDED_CELL, manifest_path=path,
                                  bench_dir=bench_dir_of(path))
    assert cell.generator == "staged_training"
    assert cell.bench_dir == str(tmp_path / "benchmarks")
    assert cell.sizes["rnn_size"] == 128 and cell.sizes["num_layers"] == 1
    assert cell.params["steps_per_dispatch"] == 8 and cell.params["slots"] == 8
    tiny = tiny_cell(ADDED_CELL, manifest_path=path)
    assert tiny.sizes["rnn_size"] == 16 and tiny.sizes["num_layers"] == 1
    assert tiny.params["steps_per_dispatch"] == 2 and tiny.params["slots"] == 2
    assert tiny.params["first_loss_rtol"] == 0.25    # the mix's, untouched

    line = rehearse(tiny, seconds=0.3)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"train_samples_per_s_per_chip", "setup_s"}
    line = rehearse(tiny, trace=True, seconds=0.3)
    assert line["correct"] is True
    assert line["metrics"][ADDED_METRIC]["value"] >= 1
    assert line["metrics"][ADDED_METRIC]["unit"] == "dispatches"
    assert 0 < line["metrics"]["train_step_mfu"]["value"] < 100
    # its kernel's roofline is listed, and silent where the kernel did not run
    assert ADDED_ROOFLINE in {m["name"] for m in tiny.per_layer}
    assert ADDED_ROOFLINE not in line["metrics"]
    # metrics of other cells are not reported here
    assert "pallas_time_share" not in line["metrics"]
    assert "lstm_seq_time_block" not in line["metrics"]
    # and what the new cell brought is not reported in the cells that were there
    old = tiny_cell("charrnn_train_1chip", manifest_path=path)
    assert ADDED_METRIC not in {m["name"] for m in old.per_layer}


def test_a_cell_without_its_presets_names_the_files_to_add(tmp_path):
    path = manifest_with_a_later_prs_additions(tmp_path)
    os.remove(tmp_path / "presets" / "cells" / (ADDED_CELL + ".json"))
    with pytest.raises(discovery.BenchmarkError,
                       match=rf"presets/cells/{ADDED_CELL}\.json"):
        tiny_cell(ADDED_CELL, manifest_path=path)
    os.remove(tmp_path / "presets" / "configs" / "charrnn_2x512.json")
    with pytest.raises(discovery.BenchmarkError,
                       match=r"presets/configs/charrnn_2x512\.json"):
        tiny_cell("charrnn_train_1chip", manifest_path=path)


# ------------------------------------------------------------ non-TPU exit
def _run_command(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), "--workload",
         "charrnn_train_1chip", "--seed", "1", "--seconds", "1", "--trace",
         "0", *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_without_a_tpu_the_command_exits_non_zero_and_prints_no_result():
    r = _run_command(REPO)
    assert r.returncode == gate.NO_TPU_EXIT
    assert "TPU" in r.stderr
    assert not [l for l in r.stdout.splitlines() if l.startswith("{")]


def test_without_the_program_the_command_exits_non_zero(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    r = _run_command(str(tmp_path))
    assert r.returncode != 0
    assert not [l for l in r.stdout.splitlines() if l.startswith("{")]
