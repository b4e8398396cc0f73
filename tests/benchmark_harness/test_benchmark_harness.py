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

from bench_presets import REPO, manifest_with_serving_cell, rehearse
from benchmarks.harness import discovery, gate, main, stats

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest():
    return discovery.load_json(os.path.join(REPO, "BENCHMARK.json"))


@pytest.fixture(params=["as_committed", "with_the_serving_cell"])
def manifest_path(request, tmp_path):
    """The manifest, and the manifest once a PR has added the decode cell's
    entries to it (the mix and its readers are here, the cell is not)."""
    if request.param == "as_committed":
        return os.path.join(REPO, "BENCHMARK.json")
    return manifest_with_serving_cell(str(tmp_path))


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
    line = main.final_line(
        correct=True, attempted=4, failed=0,
        metrics={"setup_s": {"value": 1.5, "unit": "s"}},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1})
    assert "\n" not in line
    assert set(json.loads(line)) == {"correct", "attempted", "failed",
                                     "metrics", "device"}
    traced = json.loads(main.final_line(
        correct=False, attempted=1, failed=1, metrics={}, device={},
        breakdown={"device_ops": [], "idle_gaps": []}))
    assert set(traced) == {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown"}
    assert traced["correct"] is False


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
def test_manifest_has_exactly_the_contract_keys_and_limits():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    # a full check with all 24 cells fits 43200 s
    cells = 24
    assert (2 + 14 * cells) * (m["run_seconds"] + 60) + cells * 180 + 1200 <= 43200
    assert m["command"][:2] == ["python3", "benchmarks/run.py"]
    for p in m["paths"]:
        assert os.path.isdir(os.path.join(REPO, p)) and not p.startswith("/")
    assert 2 <= len(m["workloads"]) <= 24
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


def test_manifest_names_units_and_references(manifest_path):
    m = discovery.load_json(manifest_path)
    configs = {c["name"] for c in m["configs"]}
    cells = {w["name"] for w in m["workloads"]}
    e2e = {x["name"] for x in m["end_to_end"]}
    assert "setup_s" in e2e
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in m["paths"]))
        assert os.path.isfile(os.path.join(REPO, c["file"]))
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
        cell = discovery.resolve_cell(w["name"], manifest_path=manifest_path)
        e2e = {x["name"] for x in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        # a per-layer metric is reported only where the metric it moves is
        for x in cell.per_layer:
            assert x["moves"] in e2e, (w["name"], x["name"])
            assert os.path.isfile(cell.path("layer_metrics", x["name"] + ".py"))
        assert os.path.isfile(cell.path("generators", cell.generator + ".py"))
        assert os.path.isfile(cell.path("configs", cell.config + ".py"))


def test_a_kernels_roofline_share_is_named_for_it():
    for x in manifest()["per_layer"]:
        if "roofline" in x["name"]:
            assert x["name"].endswith("_roofline") and x["unit"] == "%"


# --------------------------------------------------------------- discovery
def test_unknown_workload_is_named_in_the_error():
    with pytest.raises(discovery.BenchmarkError, match="no_such_cell"):
        discovery.resolve_cell("no_such_cell")


def test_a_cell_mix_config_and_metric_added_as_files_are_found(tmp_path):
    """A later PR adds files and manifest entries and edits nothing: the
    same harness code then runs the new cell and reads the new metric."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(os.path.join(REPO, "benchmarks"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(bench / "configs" / "charrnn_2x512.py",
                bench / "configs" / "charrnn_1x16.py")
    (bench / "configs" / "charrnn_1x16.json").write_text(json.dumps(dict(
        discovery.load_json(str(bench / "configs" / "charrnn_2x512.json")),
        name="charrnn_1x16", rnn_size=16, num_layers=1, vocab_size=12,
        classes=12)))
    (bench / "traffic" / "train_staged_short.json").write_text(json.dumps({
        "generator": "staged_training",
        "params": {"wrapper": "none", "first_loss_rtol": 0.25,
                   "reference_rtol": 0.03, "twin_rtol": [0.03],
                   "trace_seconds": 1, "batch_per_chip": 2, "seq_len": 4,
                   "slots": 2}}))
    (bench / "workloads" / "charrnn_1x16_short.json").write_text(json.dumps({
        "why": "added by a test", "params": {"steps_per_dispatch": 2}}))
    (bench / "layer_metrics" / "dispatches_in_window.py").write_text(
        "def read(run):\n    return run.result['dispatches']\n")
    m = manifest()
    m["configs"].append({"name": "charrnn_1x16", "source": "test",
                         "file": "benchmarks/configs/charrnn_1x16.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "charrnn_1x16_short",
                           "config": "charrnn_1x16",
                           "traffic": "train_staged_short", "chips": 1,
                           "why": "test"})
    m["end_to_end"][0]["workloads"].append("charrnn_1x16_short")
    m["per_layer"].append({
        "name": "dispatches_in_window", "unit": "dispatches",
        "better": "higher", "source": "program_counter",
        "layer": "entry points: fit_on_device and ParallelWrapper",
        "moves": "train_samples_per_s_per_chip",
        "workloads": ["charrnn_1x16_short"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))

    cell = discovery.resolve_cell(
        "charrnn_1x16_short", manifest_path=str(tmp_path / "BENCHMARK.json"),
        bench_dir=str(bench))
    assert cell.generator == "staged_training"
    assert cell.sizes["rnn_size"] == 16
    assert cell.params["steps_per_dispatch"] == 2 and cell.params["slots"] == 2
    line = rehearse(cell, trace=True, seconds=0.3)
    assert line["correct"] is True
    assert line["metrics"]["dispatches_in_window"]["value"] >= 1
    assert line["metrics"]["dispatches_in_window"]["unit"] == "dispatches"
    # metrics of other cells are not reported here
    assert "pallas_time_share" not in line["metrics"]


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
