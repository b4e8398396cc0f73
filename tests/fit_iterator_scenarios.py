"""The nets and the batch stream of ``test_fit_iterator_spans.py``, in a file
of their own so that the hashes the test holds can be taken again on any
commit:

    python tests/fit_iterator_scenarios.py

prints ``{(mode, kind): sha256 of the trained parameters}`` for ``fit`` over
the stream below (two full windows of ``STAGE`` batches and a ragged tail of
three). The test holds the values the parent of PR 38 printed: spans and
counters may not move a bit of what ``fit`` computes.
"""

import hashlib

import numpy as np

STAGE, BATCHES, ROWS, FEATURES, CLASSES, STEPS = 4, 11, 8, 8, 4, 6
TBPTT_FWD = 3
MODES = ("staged", "per_batch", "tbptt")
KINDS = ("mln", "graph")


def net(mode: str, kind: str):
    from deeplearning4j_tpu import (ComputationGraph,
                                    ComputationGraphConfiguration, DenseLayer,
                                    GravesLSTM, InputType,
                                    MultiLayerConfiguration, MultiLayerNetwork,
                                    OutputLayer, RnnOutputLayer, UpdaterConfig)

    updater = UpdaterConfig(updater="adam", learning_rate=1e-2)
    if mode == "tbptt":
        layers = [GravesLSTM(n_out=8, activation="tanh"),
                  RnnOutputLayer(n_out=CLASSES, activation="softmax",
                                 loss="mcxent")]
        input_type = InputType.recurrent(FEATURES, STEPS)
    else:
        layers = [DenseLayer(n_out=16, activation="relu"),
                  OutputLayer(n_out=CLASSES, activation="softmax",
                              loss="mcxent")]
        input_type = InputType.feed_forward(FEATURES)
    if kind == "mln":
        return MultiLayerNetwork(MultiLayerConfiguration(
            layers=layers, input_type=input_type, updater=updater, seed=3,
            backprop_type="tbptt" if mode == "tbptt" else "standard",
            tbptt_fwd_length=TBPTT_FWD, tbptt_back_length=TBPTT_FWD)).init()
    b = (ComputationGraphConfiguration.builder().add_inputs("in")
         .set_input_types(input_type).updater(updater).seed(3))
    prev = "in"
    for i, layer in enumerate(layers):
        b, prev = b.add_layer(f"layer{i}", layer, prev), f"layer{i}"
    b = b.set_outputs(prev)
    if mode == "tbptt":
        b = b.tbptt(TBPTT_FWD, TBPTT_FWD)
    return ComputationGraph(b.build()).init()


def batches(mode: str, seed: int = 0) -> list:
    """``BATCHES`` DataSets of float32 arrays, all of one shape."""
    from deeplearning4j_tpu.datasets.iterators import DataSet

    rng = np.random.default_rng(seed)
    lead = (ROWS, STEPS) if mode == "tbptt" else (ROWS,)
    out = []
    for _ in range(BATCHES):
        x = rng.normal(size=lead + (FEATURES,)).astype(np.float32)
        y = np.eye(CLASSES, dtype=np.float32)[rng.integers(0, CLASSES, lead)]
        out.append(DataSet(x, y))
    return out


def stage_of(mode: str) -> int:
    # TBPTT is handed the window too: the engine has to put it back to 0
    return 0 if mode == "per_batch" else STAGE


def fit(mode: str, kind: str, listeners=()):
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator

    n = net(mode, kind)
    n.listeners = list(listeners)
    n.fit(ListDataSetIterator(batches(mode)), stage_on_device=stage_of(mode))
    return n


def params_hash(n) -> str:
    import jax

    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(n.params):
        a = np.asarray(leaf)
        h.update(str((a.shape, a.dtype)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


if __name__ == "__main__":
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import conftest  # noqa: F401  the CPU backend and x64, as the tests run

    for m in MODES:
        for k in KINDS:
            print(f'    ("{m}", "{k}"): "{params_hash(fit(m, k))}",')
