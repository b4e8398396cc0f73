"""conf.params_dtype="bfloat16": carry parameters in the compute dtype
(the round-5 weight-copy-bound lever; docs/resnet50_step_analysis.md). The
default (None) keeps f32 master params with a per-step bf16 compute cast."""

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import (
    DenseLayer,
    InputType,
    MultiLayerConfiguration,
    MultiLayerNetwork,
    OutputLayer,
    UpdaterConfig,
)
from deeplearning4j_tpu.datasets.iterators import DataSet


def _data(n=64, n_in=8, k=3, seed=0):
    rng = np.random.default_rng(seed)
    labels = np.eye(k, dtype=np.float32)[rng.integers(0, k, n)]
    feats = (labels @ rng.normal(size=(k, n_in)) * 2
             + rng.normal(scale=0.2, size=(n, n_in))).astype(np.float32)
    return feats, labels


def _conf(params_dtype):
    return MultiLayerConfiguration(
        layers=[DenseLayer(n_out=16, activation="relu"),
                OutputLayer(n_out=3, activation="softmax", loss="mcxent")],
        input_type=InputType.feed_forward(8),
        updater=UpdaterConfig(updater="sgd", learning_rate=0.1),
        seed=1, dtype="bfloat16", params_dtype=params_dtype,
    )


def test_bf16_params_train_and_leaf_dtypes():
    feats, labels = _data()
    net = MultiLayerNetwork(_conf("bfloat16")).init()
    for leaf in jax.tree_util.tree_leaves(net.params):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.bfloat16
    s0 = float(net.score(DataSet(feats, labels)))
    for _ in range(15):
        net.fit(DataSet(feats, labels))
    assert float(net.score(DataSet(feats, labels))) < s0
    # params stayed bf16 through the optimizer updates
    for leaf in jax.tree_util.tree_leaves(net.params):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.bfloat16


def test_default_keeps_wide_master():
    # master params stay at full width (f32; f64 under the suite's x64 mode)
    net = MultiLayerNetwork(_conf(None)).init()
    for leaf in jax.tree_util.tree_leaves(net.params):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype in (jnp.float32, jnp.float64)
            assert leaf.dtype != jnp.bfloat16


def test_unknown_params_dtype_raises():
    import pytest

    with pytest.raises(ValueError, match="params_dtype"):
        MultiLayerNetwork(_conf("bf16")).init()  # typo must be loud


def test_params_dtype_json_round_trip():
    conf = _conf("bfloat16")
    back = MultiLayerConfiguration.from_json(conf.to_json())
    assert back.params_dtype == "bfloat16"
    assert MultiLayerConfiguration.from_json(
        _conf(None).to_json()).params_dtype is None


def test_bf16_params_compose_with_spmd_wrapper():
    """bf16 param carry x GSPMD: the data-parallel wrapper trains with
    bf16-resident params (and the dp x tp mesh still shards them)."""
    from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh

    feats, labels = _data(n=64)
    net = MultiLayerNetwork(_conf("bfloat16")).init()
    w = ParallelWrapper(net, mesh=make_mesh(8))
    s0 = float(net.score(DataSet(feats, labels)))
    for _ in range(5):
        w.fit(DataSet(feats, labels))
    assert float(net.score(DataSet(feats, labels))) < s0
    for leaf in jax.tree_util.tree_leaves(net.params):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.bfloat16

    net = MultiLayerNetwork(_conf("bfloat16")).init()
    mesh = make_mesh(8, axis_names=("data", "model"), shape=(4, 2))
    w = ParallelWrapper(net, mesh=mesh, model_axis="model")
    w._setup_sync()
    w._fit_sync(DataSet(feats, labels))
    spec = net.params[0]["W"].sharding.spec
    assert "model" in tuple(s for s in spec if s is not None), spec
    assert net.params[0]["W"].dtype == jnp.bfloat16


def test_bf16_params_survive_serialization():
    import os
    import tempfile

    from deeplearning4j_tpu.utils.serialization import (
        restore_model,
        write_model,
    )

    feats, labels = _data()
    net = MultiLayerNetwork(_conf("bfloat16")).init()
    for _ in range(3):
        net.fit(DataSet(feats, labels))
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "m.zip")
        write_model(net, path)
        back = restore_model(path)
    assert back.conf.params_dtype == "bfloat16"
    for leaf in jax.tree_util.tree_leaves(back.params):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(net.output(feats[:8]), np.float32),
        np.asarray(back.output(feats[:8]), np.float32))


def test_bf16_params_ride_the_seq_fused_kernel(monkeypatch):
    """bf16 param carry x the fused sequence kernel: an LSTM with
    bf16-resident weights dispatches the Pallas path (interpret on CPU) at
    bf16 end to end and matches the scan path."""
    from deeplearning4j_tpu import GravesLSTM, RnnOutputLayer

    def make():
        conf = MultiLayerConfiguration(
            layers=[GravesLSTM(n_out=12),
                    RnnOutputLayer(n_out=4, activation="softmax",
                                   loss="mcxent")],
            input_type=InputType.recurrent(5),
            updater=UpdaterConfig(updater="sgd", learning_rate=0.05),
            seed=6, dtype="bfloat16", params_dtype="bfloat16",
        )
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(2)
    x = rng.normal(size=(4, 7, 5)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, size=(4, 7))]
    outs = {}
    for mode in ("0", "seq"):
        monkeypatch.setenv("DL4J_TPU_PALLAS", mode)
        net = make()
        assert net.params[0]["RW"].dtype == jnp.bfloat16
        for _ in range(3):
            net.fit(DataSet(x, y))
        outs[mode] = np.asarray(net.output(x), np.float32)
    # bf16 arithmetic differs slightly between the two implementations
    np.testing.assert_allclose(outs["0"], outs["seq"], atol=2e-2)


def test_graph_params_dtype():
    from deeplearning4j_tpu.nn.conf.computation_graph import (
        ComputationGraphConfiguration,
    )
    from deeplearning4j_tpu.nn.graph.computation_graph import ComputationGraph

    conf = (ComputationGraphConfiguration.builder()
            .add_inputs("in")
            .set_input_types(InputType.feed_forward(8))
            .add_layer("d", DenseLayer(n_out=16, activation="relu"), "in")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss="mcxent"), "d")
            .set_outputs("out")
            .updater(UpdaterConfig(updater="sgd", learning_rate=0.1))
            .dtype("bfloat16").params_dtype("bfloat16")
            .build())
    g = ComputationGraph(conf).init()
    for leaf in jax.tree_util.tree_leaves(g.params):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.bfloat16
    feats, labels = _data()
    from deeplearning4j_tpu.datasets.iterators import DataSet as DS
    s0 = float(g.score(DS(feats, labels)))
    for _ in range(15):
        g.fit(DS(feats, labels))
    assert float(g.score(DS(feats, labels))) < s0
    back = ComputationGraphConfiguration.from_json(conf.to_json())
    assert back.params_dtype == "bfloat16"
