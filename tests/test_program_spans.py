"""The program's spans at the boundaries a dispatch crosses, and the names of
what runs on the device (ISSUE 26): ``dl4j.fit.*`` inside both engines'
``fit_on_device``, ``dl4j.parallel_wrapper.*`` through ``StepTimer``,
``dl4j.cm.*`` inside the compile manager, ``name=`` on every Mosaic kernel,
and a ``jax.named_scope`` per layer, vertex, ``loss`` and
``optimizer_update`` in the lowered training programs."""

import ast
import os

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import (
    ComputationGraph,
    ComputationGraphConfiguration,
    DenseLayer,
    InputType,
    MultiLayerConfiguration,
    MultiLayerNetwork,
    OutputLayer,
    UpdaterConfig,
)
from deeplearning4j_tpu.telemetry import get_recorder

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "deeplearning4j_tpu")
FIT_CHILDREN = ["dl4j.fit.prepare", "dl4j.fit.launch", "dl4j.fit.fetch",
                "dl4j.fit.listeners"]


def _mln():
    conf = MultiLayerConfiguration(
        layers=[DenseLayer(n_out=16, activation="relu"),
                OutputLayer(n_out=4, activation="softmax", loss="mcxent")],
        input_type=InputType.feed_forward(8),
        updater=UpdaterConfig(updater="adam", learning_rate=1e-3), seed=3)
    return MultiLayerNetwork(conf)


def _graph():
    conf = (ComputationGraphConfiguration.builder()
            .add_inputs("in")
            .set_input_types(InputType.feed_forward(8))
            .updater(UpdaterConfig(updater="sgd", learning_rate=0.1))
            .seed(3)
            .add_layer("hidden", DenseLayer(n_out=16, activation="relu"), "in")
            .add_layer("out", OutputLayer(n_out=4, activation="softmax",
                                          loss="mcxent"), "hidden")
            .set_outputs("out")
            .build())
    return ComputationGraph(conf)


def _staged(slots=3, batch=8, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(slots, batch, 8)).astype(np.float32)
    ys = np.eye(4, dtype=np.float32)[rng.integers(0, 4, (slots, batch))]
    return xs, ys


def _events_since(mark):
    return get_recorder().events[mark:]


def _mark():
    return len(get_recorder().events)


@pytest.mark.parametrize("make,kind", [(_mln, "mln"), (_graph, "graph")])
def test_fit_on_device_leaves_a_dispatch_span_with_its_four_children(
        make, kind):
    net = make().init()
    xs, ys = _staged()
    dispatches = []
    for _ in range(2):
        mark = _mark()
        losses = net.fit_on_device(xs, ys, steps=5)
        events = [e for e in _events_since(mark)
                  if e["name"].startswith("dl4j.fit.")]
        root = events[-1]  # a parent closes after its children
        assert root["name"] == "dl4j.fit.dispatch"
        assert root["args"]["parent"] is None
        assert root["args"]["net"] == kind
        assert (root["args"]["steps"], root["args"]["slots"],
                root["args"]["batch"]) == (len(losses), 3, 8)
        children = events[:-1]
        assert [e["name"] for e in children] == FIT_CHILDREN
        for e in children:
            assert e["args"]["parent"] == "dl4j.fit.dispatch"
            assert e["args"]["dispatch"] == root["args"]["dispatch"]
            # one clock: a child lies inside its parent
            assert root["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= root["ts"] + root["dur"]
        dispatches.append(root["args"]["dispatch"])
    assert dispatches[0] != dispatches[1]


def test_the_wrapper_nests_the_dispatch_under_its_step_phase():
    from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh

    assert len(jax.devices()) >= 4  # conftest forces virtual CPU devices
    net = _mln().init()
    wrapper = ParallelWrapper(net, mesh=make_mesh(4))
    xs, ys = _staged()
    mark = _mark()
    wrapper.fit_on_device(xs, ys, steps=3)
    events = {e["name"]: e for e in _events_since(mark)}
    data, step = (events["dl4j.parallel_wrapper.data"],
                  events["dl4j.parallel_wrapper.step"])
    dispatch = events["dl4j.fit.dispatch"]
    assert data["args"]["parent"] is None and step["args"]["parent"] is None
    assert dispatch["args"]["parent"] == "dl4j.parallel_wrapper.step"
    assert dispatch["args"]["dispatch"] == step["args"]["dispatch"]
    assert events["dl4j.fit.launch"]["args"]["dispatch"] \
        == step["args"]["dispatch"]
    assert data["ts"] + data["dur"] <= step["ts"]  # the put comes first
    # StepTimer's own totals stay: the UI and TrainingMaster read them
    assert set(wrapper.timer.breakdown()) == {"data", "step"}


def test_a_compile_manager_miss_records_lower_compile_admission_a_hit_none():
    net = _mln().init()
    xs, ys = _staged(slots=2)
    mark = _mark()
    net.fit_on_device(xs, ys)
    miss = [e for e in _events_since(mark) if e["name"].startswith("dl4j.cm.")]
    assert {"dl4j.cm.lower", "dl4j.cm.compile", "dl4j.cm.admission"} \
        == {e["name"] for e in miss}
    for e in miss:
        assert e["args"]["kind"] == "mln_multi_step"
        assert e["args"]["parent"] == "dl4j.fit.prepare"  # nests where it ran
    order = [e["name"] for e in miss if e["name"] != "dl4j.cm.admission"]
    assert order == ["dl4j.cm.lower", "dl4j.cm.compile"]
    mark = _mark()
    net.fit_on_device(xs, ys)
    assert not [e for e in _events_since(mark)
                if e["name"].startswith("dl4j.cm.")]


def test_net_init_is_a_span_and_a_second_init_is_not():
    for make, kind in ((_mln, "mln"), (_graph, "graph")):
        net = make()
        mark = _mark()
        net.init()
        net.init()  # already initialised: returns at once
        inits = [e for e in _events_since(mark)
                 if e["name"] == "dl4j.net.init"]
        assert len(inits) == 1 and inits[0]["args"]["net"] == kind


def test_the_package_counts_its_import_seconds():
    import deeplearning4j_tpu

    assert 0.0 < deeplearning4j_tpu.import_seconds < 600.0


def _pallas_calls():
    """Every ``pallas_call(...)`` in the package as (file, line, name=)."""
    found = []
    for root, _, files in os.walk(PACKAGE):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(root, fn)
            with open(path, encoding="utf-8") as fh:
                tree = ast.parse(fh.read(), filename=path)
            for node in ast.walk(tree):
                if isinstance(node, ast.Call) and (
                        getattr(node.func, "attr", None) == "pallas_call"
                        or getattr(node.func, "id", None) == "pallas_call"):
                    name = next((kw.value for kw in node.keywords
                                 if kw.arg == "name"), None)
                    found.append((os.path.relpath(path, PACKAGE), node.lineno,
                                  name))
    return found


def _literal_names(node):
    """The string literals ``name=`` can take: one, or a choice between
    literals under a static flag (one body serving the masked twin too)."""
    if isinstance(node, ast.IfExp):
        return _literal_names(node.body) + _literal_names(node.orelse)
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    return [None]


def test_every_pallas_call_has_a_name_of_its_own():
    calls = _pallas_calls()
    assert len(calls) >= 12
    unnamed = [(f, ln) for f, ln, name in calls if name is None]
    assert not unnamed, f"pallas_call without name=: {unnamed}"
    names = []
    for f, ln, name in calls:
        literals = _literal_names(name)
        assert None not in literals, \
            f"{f}:{ln}: name= must be string literals (the trace is read by it)"
        names += literals
    assert len(names) == len(set(names)) >= 15, sorted(names)
    assert {"lstm_seq_fwd", "lstm_seq_bwd", "lstm_seq_lean",
            "lstm_seq_masked_fwd", "lstm_seq_masked_bwd", "softmax_xent_fwd",
            "softmax_xent_bwd", "adam_update", "flash_fwd"} <= set(names)


def _lowered_staged_text(net, xs, ys):
    """The lowered text, with locations, of the staged program that
    ``fit_on_device(xs, ys)`` would run."""
    if isinstance(net, ComputationGraph):
        xs, ys = [xs], [ys]
    steps_cap, with_masks, _, args = net._staged_args(
        xs, ys, None, None, None, None)
    jitted = net._build_multi_step(steps_cap, with_masks, False)
    return jitted.lower(*args).as_text(debug_info=True)


def _tiny_char_rnn():
    from deeplearning4j_tpu.models.char_rnn import char_rnn

    conf = char_rnn(12, hidden_size=16, num_layers=2, seed=1)
    conf.backprop_type = "standard"
    net = MultiLayerNetwork(conf).init()
    idx = np.random.default_rng(0).integers(0, 12, (2, 4, 9))
    onehot = np.eye(12, dtype=np.float32)[idx]
    scopes = [net.layer_scope(i) for i in range(len(conf.layers))]
    return (net, onehot[:, :, :-1], onehot[:, :, 1:], scopes[:-1], scopes[-1],
            "dl4j_mln_staged")


def _tiny_resnet():
    from deeplearning4j_tpu.models.resnet import resnet_conf

    conf = resnet_conf([1, 1], bottleneck=True, num_classes=10,
                       image_size=(32, 32), channels=3, seed=1)
    net = ComputationGraph(conf).init()
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(2, 2, 32, 32, 3)).astype(np.float32)
    ys = np.eye(10, dtype=np.float32)[rng.integers(0, 10, (2, 2))]
    # vertices without an operation of their own (a pass-through) leave no
    # scope; every layer vertex with parameters must
    (out,) = conf.network_outputs
    scopes = [n for n in net._topo
              if n != out and jax.tree_util.tree_leaves(net.params[n])]
    return net, xs, ys, scopes, out, "dl4j_graph_staged"


@pytest.mark.parametrize("make", [_tiny_char_rnn, _tiny_resnet])
def test_the_lowered_step_carries_layer_loss_and_optimizer_scopes(make):
    net, xs, ys, scopes, out, program = make()
    text = _lowered_staged_text(net, xs, ys)
    assert f"jit({program})" in text  # the module says which program it is
    assert len(scopes) >= 2
    for scope in scopes:
        # forward under jvp(<scope>), backward under transpose(jvp(<scope>))
        assert f"/jvp({scope})/" in text, scope
        assert f"/transpose(jvp({scope}))/" in text, scope
    # the output layer's loss is its scope inside ``loss``
    assert f"/jvp(loss)/{out}/" in text
    assert f"/transpose(jvp(loss))/{out}/" in text
    assert "/optimizer_update/" in text
