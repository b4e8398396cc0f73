"""On-device training loop: N optimizer steps in ONE device dispatch.

The TPU-first answer to the reference's per-minibatch fit loop
(MultiLayerNetwork.fit:917): `fit_on_device` stages K batches in HBM and
`lax.scan`s the jitted train step over them, so the host dispatches once per
LOOP instead of once per STEP: the host leaves the hot path entirely (one
dispatch is a ~0.6 ms round trip on the v5e — PERF.md — which a short step
cannot hide). Numerics
are bit-identical to per-step fit — same RNG split chain — which this
example verifies, then shows the same API running data-parallel over the
whole mesh via ParallelWrapper (gradient psums ride ICI *inside* the scan).
"""

import argparse

import numpy as np


def _conf(seed=7):
    from deeplearning4j_tpu import (
        DenseLayer,
        InputType,
        MultiLayerConfiguration,
        OutputLayer,
        UpdaterConfig,
    )

    return MultiLayerConfiguration(
        layers=[DenseLayer(n_out=64, activation="relu"),
                OutputLayer(n_out=5, activation="softmax", loss="mcxent")],
        input_type=InputType.feed_forward(12),
        updater=UpdaterConfig(updater="adam", learning_rate=3e-3),
        seed=seed,
    )


def main(quick: bool = False):
    import jax

    from deeplearning4j_tpu import MultiLayerNetwork
    from deeplearning4j_tpu.datasets.iterators import DataSet
    from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh

    rng = np.random.default_rng(0)
    w = rng.normal(size=(12, 5))
    k, b = 8, 64  # K staged batches of b examples
    xs = rng.normal(size=(k, b, 12)).astype(np.float32)
    ys = np.eye(5, dtype=np.float32)[(xs @ w).argmax(-1)]
    steps = 2 * k if quick else 10 * k  # cycles i % K through the batches

    # 1) one dispatch for the whole loop
    net = MultiLayerNetwork(_conf()).init()
    losses = net.fit_on_device(xs, ys, steps=steps)
    acc = net.evaluate([DataSet(xs[0], ys[0])]).accuracy()
    print(f"on-device loop: {steps} steps in 1 dispatch, "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}, accuracy={acc:.3f}")

    # 2) bit-parity with the sequential per-step path
    seq = MultiLayerNetwork(_conf()).init()
    for i in range(steps):
        seq.fit(DataSet(xs[i % k], ys[i % k]))
    for a, s in zip(jax.tree_util.tree_leaves(net.params),
                    jax.tree_util.tree_leaves(seq.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(s),
                                   atol=1e-6, rtol=1e-5)
    print("parity: on-device params == sequential params")

    # 3) same API, data-parallel over the mesh: batch dim shards over the
    # "data" axis; gradient all-reduce happens inside the scanned step
    n_dev = len(jax.devices())
    dp_net = MultiLayerNetwork(_conf()).init()
    wrapper = ParallelWrapper(dp_net, mesh=make_mesh(n_dev), averaging_frequency=1)
    dp_losses = wrapper.fit_on_device(xs, ys, steps=steps)
    print(f"data-parallel over {n_dev} devices: "
          f"loss {dp_losses[0]:.3f} -> {dp_losses[-1]:.3f}; "
          f"phase timings: {wrapper.timer.breakdown()}")
    return acc


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    main(ap.parse_args().quick)
