"""One training engine (``nn/engine.py``) under both net classes.

Every case runs on a small ``MultiLayerNetwork`` and on the same layers as a
``ComputationGraph``: what the engine does for one class it does for the
other, and neither class may grow a copy of its own again.
"""

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import (
    GravesLSTM,
    InputType,
    MultiLayerConfiguration,
    MultiLayerNetwork,
    RnnOutputLayer,
    UpdaterConfig,
)
from deeplearning4j_tpu.nn import engine
from deeplearning4j_tpu.nn.conf.computation_graph import ComputationGraphConfiguration
from deeplearning4j_tpu.nn.graph.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn.layers.moe import DroplessExpertsLayer

KINDS = ("mln", "graph")
CLASSES = {"mln": MultiLayerNetwork, "graph": ComputationGraph}
B, T, F, C = 4, 6, 5, 3

# everything from the jitted step to the fit loop: defined once, in the engine
ENGINE_METHODS = (
    "_build_train_step", "_build_multi_step", "_staged_out_constraint",
    "_staged_executable", "_staged_args", "warmup", "fit_on_device", "fit",
    "_fit_epoch_staged", "_check_padding_waste", "_fit_batch",
    "_build_tbptt_step", "_fit_tbptt", "_invalidate_compiled",
    "_kernel_scoped", "_step_callable", "set_listeners", "add_listener",
    "set_telemetry", "_wants_grad_stats", "num_params", "memory_report",
    "preflight", "analyze_ir", "clone")


def _layers():
    # vertex names below are the sequential class's layer scopes
    return [GravesLSTM(n_out=16, activation="tanh"),
            DroplessExpertsLayer(n_out=16, n_experts=8, top_k=2, hidden=8),
            RnnOutputLayer(n_out=C, activation="softmax", loss="mcxent")]


def _net(kind, tbptt=False):
    updater = UpdaterConfig(updater="adam", learning_rate=1e-2)
    if kind == "mln":
        return MultiLayerNetwork(MultiLayerConfiguration(
            layers=_layers(), input_type=InputType.recurrent(F, T),
            updater=updater, seed=3,
            backprop_type="tbptt" if tbptt else "standard",
            tbptt_fwd_length=3, tbptt_back_length=2)).init()
    b = (ComputationGraphConfiguration.builder().add_inputs("in")
         .set_input_types(InputType.recurrent(F, T)))
    prev = "in"
    for i, layer in enumerate(_layers()):
        b, prev = b.add_layer(f"layer{i}", layer, prev), f"layer{i}"
    b = b.set_outputs(prev).updater(updater)
    return ComputationGraph((b.tbptt(3, 2) if tbptt else b).build()).init()


def _batches(k=2, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(k, B, T, F)).astype(np.float32)
    ys = np.eye(C, dtype=np.float32)[rng.integers(0, C, size=(k, B, T))]
    return xs, ys


def _own_form(kind, *arrays):
    """One batch's arrays as the class's jitted steps take them."""
    return arrays if kind == "mln" else tuple([a] for a in arrays)


@pytest.mark.parametrize("kind", KINDS)
def test_the_net_classes_inherit_the_engine_and_override_none_of_it(kind):
    cls = CLASSES[kind]
    assert issubclass(cls, engine.TrainingEngine)
    for name in ENGINE_METHODS:
        assert name in vars(engine.TrainingEngine), name
        owners = [c.__name__ for c in cls.__mro__ if name in vars(c)]
        assert owners == ["TrainingEngine"], (name, owners)


@pytest.mark.parametrize("kind", KINDS)
def test_layer_counters_are_published_and_start_each_dispatch_from_zero(kind):
    from deeplearning4j_tpu.telemetry import get_registry
    from deeplearning4j_tpu.telemetry.device import LAYER_COUNTER_FAMILY

    def counted():
        fam = get_registry().snapshot().get(LAYER_COUNTER_FAMILY,
                                            {"values": []})
        return {r["labels"]["counter"]: r["value"] for r in fam["values"]
                if r["labels"]["layer"] == "layer1"}

    def held(net):
        (state,) = (s for n, _, s in net._layer_states() if n == "layer1")
        return dict(zip(DroplessExpertsLayer.COUNTERS,
                        (int(v) for v in state["counters"])))

    net = _net(kind)
    xs, ys = _batches()
    before = counted()
    net.fit_on_device(xs, ys, steps=3)
    added = {k: v - before.get(k, 0.0) for k, v in counted().items()}
    assert added["tokens"] == 3 * B * T
    assert added["rows_held"] == 3 * B * T * 2  # every expert held, top 2
    assert added["rows_dropped"] == 0
    # the dispatch's sums are what the state holds after it
    assert held(net) == {k: int(v) for k, v in added.items()}
    # a second dispatch counts from zero again
    net.fit_on_device(xs, ys, steps=1)
    assert held(net)["tokens"] == B * T


@pytest.mark.parametrize("kind", KINDS)
def test_the_tbptt_steps_updater_lies_under_optimizer_update(kind):
    net = _net(kind, tbptt=True)
    xs, ys = _batches()
    x, y = _own_form(kind, xs[0, :, :3], ys[0, :, :3])
    text = net._build_tbptt_step().lower(
        net.params, net.opt_state, net.state, net._init_rnn_states(B),
        x, y, net._rng, None, None).as_text(debug_info=True)
    assert f"jit(dl4j_{kind}_tbptt_step)" in text
    assert "/optimizer_update/" in text
    # and the step trains: back length 2 of 3 takes the pre-segment forward
    before = jax.tree_util.tree_map(np.asarray, net.params)
    net.fit((xs[0], ys[0]))
    assert net.iteration == T // 3
    assert any(not np.array_equal(a, np.asarray(b)) for a, b in zip(
        jax.tree_util.tree_leaves(before),
        jax.tree_util.tree_leaves(net.params)))


@pytest.mark.parametrize("kind", KINDS)
def test_program_names_and_compile_manager_kinds_carry_the_class(kind):
    from deeplearning4j_tpu.runtime.compile_manager import get_compile_manager

    net = _net(kind)
    xs, ys = _batches()
    x, y = _own_form(kind, xs[0], ys[0])
    step = net._build_train_step().lower(
        net.params, net.opt_state, net.state, x, y, net._rng, None, None)
    assert f"jit(dl4j_{kind}_train_step)" in step.as_text(debug_info=True)
    steps_cap, with_masks, _, args = net._staged_args(
        *_own_form(kind, xs, ys), None, None, None, None)
    staged = net._build_multi_step(steps_cap, with_masks, False).lower(*args)
    assert f"jit(dl4j_{kind}_staged)" in staged.as_text(debug_info=True)

    net.fit_on_device(xs, ys)
    net.fit((xs[0], ys[0]))
    kinds = {key[1] for key in get_compile_manager()._entries
             if key[0] == net._cm_token}
    assert kinds == {f"{kind}_multi_step", f"{kind}_train_step"}


def test_apply_step_is_the_one_place_that_scales_the_loss():
    """``scaled_loss`` has one caller under ``nn/`` and ``parallel/wrapper.py``
    (``parallel/pipeline.py`` updates inside a shard_map region of its own)."""
    import pathlib
    import re

    import deeplearning4j_tpu

    root = pathlib.Path(deeplearning4j_tpu.__file__).parent
    files = [*root.glob("nn/**/*.py"), root / "parallel" / "wrapper.py"]
    calls = [f"{p.relative_to(root)}:{i}"
             for p in files for i, line in enumerate(p.read_text().splitlines(), 1)
             if re.search(r"\bscaled_loss\(", line) and "def " not in line]
    assert len(calls) == 1 and calls[0].startswith("nn/engine.py:"), calls
