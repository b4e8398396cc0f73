"""``updater_in_place_share`` (PR 33): the bytes of the fused Adam kernel's
results that the compiled program ties to an operand, over the bytes of all
its results, read from the programs' text. The instruction lines are the
v5e compiler's own spelling, cut to what the reader looks at."""

import os

import pytest

from bench_presets import REPO, rehearse, tiny_cell
from benchmarks.harness import scopes
from benchmarks.harness.discovery import load_json, load_module

NAME = "updater_in_place_share"
LEAF = "f32[14848,2688]{1,0:T(8,128)}"
SMALL = "f32[21,128]{1,0:T(8,128)S(1)}"
CALL = ('  %{name} = ({s}, {s}, {s}) custom-call(%g, %m, %v, %scalars), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{{f32[3]{{0}}}}{tied}, metadata={{op_name="jit(dl4j_graph_staged)/'
        'while/body/closed_call/optimizer_update/adam_update/pallas_call"}}')
ALL_THREE = ", output_to_operand_aliasing={{0}: (0, {}), {1}: (1, {}), " \
            "{2}: (2, {})}"
MOMENTS = ", output_to_operand_aliasing={{1}: (1, {}), {2}: (2, {})}"
OTHER = ('  %flash_fwd.14 = (bf16[32,8192,128]{2,1,0}, f32[32,1,8192]{2,1,0})'
         ' custom-call(%q, %k), custom_call_target="tpu_custom_call", '
         'output_to_operand_aliasing={{0}: (0, {})}')
ONE_RESULT = ('  %adam_update.9 = f32[8,128]{1,0} custom-call(%a), '
              'custom_call_target="tpu_custom_call", '
              'output_to_operand_aliasing={{}: (0, {})}')


def call(name, shape, tied=""):
    return CALL.format(name=name, s=shape, tied=tied)


@pytest.fixture(scope="module")
def metric():
    return load_module(os.path.join(REPO, "benchmarks", "layer_metrics",
                                    NAME + ".py"))


@pytest.mark.parametrize("texts,value", [
    # every result of every call tied: the change
    (["\n".join([call("adam_update.12", LEAF, ALL_THREE), OTHER,
                 call("adam_update.13", SMALL, ALL_THREE)])], 100.0),
    # no call says a word about its results: the parent
    (["\n".join([call("adam_update.12", LEAF), OTHER])], 0.0),
    # a program with no such call (the CPU, a mesh, SGD): optax ran
    ([OTHER, "  %add.1 = f32[8]{0} add(%x, %y)"], 0.0),
    ([], 0.0),
    # the moments tied and the first result not: two thirds, by bytes
    ([call("adam_update.12", LEAF, MOMENTS)], 200.0 / 3),
    # ... weighed by bytes over the programs of the run
    ([call("adam_update.1", LEAF, ALL_THREE), call("adam_update.2", LEAF)],
     50.0),
    # a call with one result names it ``{}``
    ([ONE_RESULT], 100.0),
    # a program that does not offer its text: nothing
    (None, None),
])
def test_share_of_the_adam_calls_result_bytes_tied_to_an_operand(
        metric, monkeypatch, texts, value):
    monkeypatch.setattr(scopes, "program_texts", lambda: texts)
    got = metric.read(object())
    assert got == (None if value is None else pytest.approx(value))


def test_result_bytes_are_read_from_the_instructions_own_shapes(metric):
    line = call("adam_update.12", LEAF, ALL_THREE).strip()
    assert metric.result_bytes(line) == [14848 * 2688 * 4] * 3
    assert metric.tied_and_all_bytes([line]) == (3 * 14848 * 2688 * 4,) * 2
    assert metric.result_bytes(ONE_RESULT.strip()) == [8 * 128 * 4]


def test_the_entry_is_the_manifests_last_and_lists_the_adam_cells():
    manifest = load_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == [{
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Pallas kernels",
        "moves": "train_samples_per_s_per_chip",
        "workloads": ["charrnn_train_1chip", "nemotron3_nano_train_1chip"]}]


@pytest.mark.parametrize("name", ["charrnn_train_1chip",
                                  "nemotron3_nano_train_1chip"])
def test_the_cpu_rehearsal_reads_zero_not_nothing(name):
    """The CPU's programs hold no ``adam_update`` call (optax runs), and 0.0
    is a reading: the cells' presets need not name the metric silent."""
    line = rehearse(tiny_cell(name), trace=True, seconds=1.0)
    assert line["metrics"][NAME] == {"value": 0.0, "unit": "%"}
