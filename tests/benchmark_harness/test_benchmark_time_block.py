"""``lstm_seq_time_block`` (PR 27): the time block the seq-fused LSTM kernels
run at, read from the program's selection log as ``fused_sites`` is."""

import os
import types

import pytest

from bench_presets import REPO, rehearse, tiny_cell
from benchmarks.harness.discovery import load_json, load_module

NAME = "lstm_seq_time_block"


def manifest():
    return load_json(os.path.join(REPO, "BENCHMARK.json"))


@pytest.fixture(scope="module")
def read():
    return load_module(os.path.join(REPO, "benchmarks", "layer_metrics",
                                    NAME + ".py")).read


def run_with(log):
    program = {} if log is None else {"selection_log": log}
    return types.SimpleNamespace(result={"program": program})


def lstm(variant, mode="auto", **more):
    return {"site": "lstm_seq", "variant": variant, "mode": mode, **more}


@pytest.mark.parametrize("log,value", [
    # both layers of the char-RNN share one record; the twin's is left out
    ([lstm("seqfused", time_block=8), lstm("reference", mode="reference"),
      {"site": "optimizer", "variant": "fused", "mode": "auto"}], 8.0),
    # shapes that differ (a TBPTT tail): the smallest block
    ([lstm("seqfused", time_block=5), lstm("seqfused", time_block=2)], 2.0),
    ([lstm("seqfused", time_block=1)], 1.0),
    # the CPU and a mesh take the XLA path; the per-step cell has no block
    ([lstm("reference")], 0.0),
    ([lstm("seqfused", time_block=8), lstm("fusedcell")], 0.0),
    # a program from before the blocking chose the kernels and says no block
    ([lstm("seqfused")], None),
    # no LSTM site (ResNet-50), no log at all
    ([{"site": "optimizer", "variant": "reference", "mode": "auto"}], None),
    ([], None),
    (None, None),
])
def test_the_block_is_the_lstm_seq_selections_own(read, log, value):
    assert read(run_with(log)) == value


def test_the_entry_lists_the_cell_that_runs_the_kernels():
    per_layer = manifest()["per_layer"]
    names = [m["name"] for m in per_layer]
    # admitted by PR 29, after what PR 23 and PR 26 declared
    assert names.count(NAME) == 1 and names.index(NAME) >= 23
    entry = per_layer[names.index(NAME)]
    fused = next(m for m in per_layer if m["name"] == "fused_sites")
    assert entry == {
        "name": NAME, "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "Pallas kernels",
        "moves": fused["moves"], "workloads": ["charrnn_train_1chip"]}
    roofline = next(m for m in per_layer if m["name"] == "lstm_seq_roofline")
    assert (entry["layer"], entry["moves"], entry["workloads"]) == (
        roofline["layer"], roofline["moves"], roofline["workloads"])


def test_the_traced_rehearsal_reports_it_beside_fused_sites():
    """On the CPU ``auto`` takes the XLA path: no site is fused, no block."""
    from deeplearning4j_tpu.ops import kernel_select as ks

    ks.reset()  # the log is the process's: earlier tests' selections go
    cell = tiny_cell("charrnn_train_1chip")
    assert NAME in {m["name"] for m in cell.per_layer}
    line = rehearse(cell, trace=True, seconds=0.5)
    assert line["correct"] is True
    assert line["metrics"]["fused_sites"]["value"] == 0
    assert line["metrics"][NAME] == {"value": 0.0, "unit": "steps"}


@pytest.mark.parametrize("name", ["resnet50_train_1chip", "resnet50_train_dp4"])
def test_cells_without_the_kernels_do_not_list_it(name):
    cell = tiny_cell(name)
    assert NAME not in {m["name"] for m in cell.per_layer}


def test_the_program_s_own_record_is_what_the_reader_finds():
    """The key the reader looks for is the one ``kernel_select`` writes."""
    from deeplearning4j_tpu.ops import kernel_select as ks

    ks.reset()
    try:
        ks.set_force_available(True)
        ctx = {"T": 256, "B": 64, "H": 512, "itemsize": 2, "acts_ok": True,
               "masked": False}
        assert ks.select("lstm_seq", ctx, forced="seqfused") == "seqfused"
        log = ks.selection_log()
    finally:
        ks.reset()
    value = load_module(os.path.join(
        REPO, "benchmarks", "layer_metrics", NAME + ".py")).read(run_with(log))
    assert value == float(log[-1]["time_block"]) > 1.0
