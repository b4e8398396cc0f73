"""AOT-bucketed inference fast path (ISSUE 7 tentpole + satellites).

Acceptance core, pinned here:

- **Bucketed parity** — padded/masked bucket dispatch is BIT-EXACT vs the
  legacy per-shape ``jax.jit`` path on dense, recurrent (ragged time), and
  graph nets; BatchNormalization models skip row padding and stay exact.
- **Zero warm-request compiles** — mixed request shapes share the pow2
  bucket executables; proven by BOTH the compile-manager counter and
  ``jax.monitoring``'s backend_compile events (the ground truth the
  manager cannot fake — same counting style as tests/test_compile_manager).
- **Boundary dtype canonicalization** (satellite) — f64/host-dtype inputs
  reuse the f32 executable instead of minting a second program.
- **Fused argmax** (satellite) — ``predict()`` transfers int32 class
  indices only, and matches the logits argmax exactly.
- **rnn_time_step continuity** — streaming state is bit-exact across
  bucketed multi-step and single-step calls.
"""

import numpy as np
import pytest

import jax

from deeplearning4j_tpu import (
    BatchNormalization,
    ComputationGraph,
    DenseLayer,
    InputType,
    MultiLayerConfiguration,
    MultiLayerNetwork,
    OutputLayer,
    UpdaterConfig,
)
from deeplearning4j_tpu.nn.conf.computation_graph import (
    ComputationGraphConfiguration,
)
from deeplearning4j_tpu.nn.layers.recurrent import GravesLSTM, RnnOutputLayer
from deeplearning4j_tpu.runtime.compile_manager import get_compile_manager
from deeplearning4j_tpu.runtime import inference as inf


class _BackendCompileCounter:
    """Ground-truth XLA compile counter via jax.monitoring (one armed
    process-wide instance; listeners cannot be unregistered on this jax)."""

    _instance = None

    def __init__(self):
        from jax import monitoring

        self.count = 0
        self.armed = False
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, *a, **kw):
        if self.armed and "backend_compile" in name:
            self.count += 1

    @classmethod
    def get(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def window(self):
        self.armed = True
        self.count = 0
        return self

    def stop(self) -> int:
        self.armed = False
        return self.count


@pytest.fixture
def legacy_env(monkeypatch):
    """Context helper: run a callable on the legacy (pre-PR7) path."""

    def run(fn):
        monkeypatch.setenv(inf.INFER_ENV, "legacy")
        try:
            return fn()
        finally:
            monkeypatch.delenv(inf.INFER_ENV, raising=False)

    return run


def _f32(net):
    """Pin params to float32 — the production compute dtype. The x64 test
    env initializes f64 params, and f64 XLA CPU kernels may pick a
    shape-dependent reduction order (1-ulp wobble between a padded and an
    unpadded program); the bit-exactness contract is stated for the
    production dtype."""
    f32 = jax.tree_util.tree_map(
        lambda a: a.astype(np.float32)
        if np.issubdtype(np.asarray(a).dtype, np.floating) else a,
        net.params)
    return net.init(params=f32)


def _dense_net(n_in=5, seed=7):
    return _f32(MultiLayerNetwork(MultiLayerConfiguration(
        layers=[DenseLayer(n_out=16, activation="relu"),
                OutputLayer(n_out=3, activation="softmax", loss="mcxent")],
        input_type=InputType.feed_forward(n_in),
        updater=UpdaterConfig(updater="adam", learning_rate=1e-2),
        seed=seed)).init())


def _rnn_net(n_in=6, seed=3):
    return _f32(MultiLayerNetwork(MultiLayerConfiguration(
        layers=[GravesLSTM(n_out=12),
                RnnOutputLayer(n_out=4, activation="softmax", loss="mcxent")],
        input_type=InputType.recurrent(n_in),
        updater=UpdaterConfig(updater="adam", learning_rate=1e-2),
        seed=seed)).init())


def _graph_net(n_in=4, seed=5):
    return _f32(ComputationGraph(
        ComputationGraphConfiguration.builder()
        .add_inputs("in")
        .add_layer("h", DenseLayer(n_out=8, activation="relu"), "in")
        .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                      loss="mcxent"), "h")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(n_in))
        .build()).init())


class TestBucketedParity:
    def test_dense_padded_rows_bit_exact(self, rng, legacy_env):
        net = _dense_net()
        x = rng.normal(size=(7, 5)).astype(np.float32)  # bucket: 8 rows
        fast = np.asarray(net.output(x))
        ref = np.asarray(legacy_env(lambda: net.output(x)))
        assert fast.shape == ref.shape == (7, 3)
        np.testing.assert_array_equal(fast, ref)

    def test_dense_per_row_unbatched_parity(self, rng, legacy_env):
        """Bucketed batch output == every row served alone (the serving
        coalescing contract). Different row counts are different programs
        (XLA:CPU picks a gemv for one row, a gemm for eight), so equality
        holds to the last ulp or two of f32, not bit-for-bit."""
        net = _dense_net()
        x = rng.normal(size=(6, 5)).astype(np.float32)
        fast = np.asarray(net.output(x))
        for i in range(x.shape[0]):
            row = np.asarray(legacy_env(lambda: net.output(x[i:i + 1])))
            np.testing.assert_allclose(fast[i:i + 1], row, rtol=5e-7, atol=0)

    def test_recurrent_ragged_time_bit_exact(self, rng, legacy_env):
        net = _rnn_net()
        x = rng.normal(size=(3, 7, 6)).astype(np.float32)  # T=7 -> bucket 8
        fast = np.asarray(net.output(x))
        ref = np.asarray(legacy_env(lambda: net.output(x)))
        assert fast.shape == ref.shape == (3, 7, 4)
        np.testing.assert_array_equal(fast, ref)

    def test_graph_bit_exact(self, rng, legacy_env):
        net = _graph_net()
        x = rng.normal(size=(5, 4)).astype(np.float32)
        fast = np.asarray(net.output(x))
        ref = np.asarray(legacy_env(lambda: net.output(x)))
        np.testing.assert_array_equal(fast, ref)

    def test_batchnorm_skips_row_padding(self, rng, legacy_env):
        """BN couples rows through batch statistics: the fast path must
        keep the exact request row count (padding would change every real
        row's output) and still match legacy bit-exactly."""
        net = _f32(MultiLayerNetwork(MultiLayerConfiguration(
            layers=[DenseLayer(n_out=8, activation="relu"),
                    BatchNormalization(),
                    OutputLayer(n_out=3, activation="softmax",
                                loss="mcxent")],
            input_type=InputType.feed_forward(5),
            updater=UpdaterConfig(updater="adam", learning_rate=1e-2),
            seed=11)).init())
        assert not net._pad_examples_ok()
        x = rng.normal(size=(7, 5)).astype(np.float32)
        fast = np.asarray(net.output(x))
        ref = np.asarray(legacy_env(lambda: net.output(x)))
        np.testing.assert_array_equal(fast, ref)

    def test_features_mask_passthrough(self, rng, legacy_env):
        """A user-supplied mask extends over the padded region and the
        real-region outputs stay bit-exact."""
        net = _rnn_net()
        x = rng.normal(size=(3, 6, 6)).astype(np.float32)  # T=6 -> bucket 8
        mask = np.ones((3, 6), np.float32)
        mask[1, 4:] = 0.0
        fast = np.asarray(net.output(x, features_mask=mask))
        ref = np.asarray(legacy_env(
            lambda: net.output(x, features_mask=mask)))
        np.testing.assert_array_equal(fast, ref)


class TestZeroWarmCompiles:
    def test_mixed_request_shapes_reuse_buckets(self, rng):
        """The acceptance pin: after one request per bucket, mixed request
        shapes pay ZERO further compiles — by the manager counter AND the
        jax.monitoring backend_compile ground truth."""
        net = _dense_net(seed=19)
        cm = get_compile_manager()
        # warm the 8-row bucket (covers rows 5..8)
        net.output(rng.normal(size=(8, 5)).astype(np.float32))
        counter = _BackendCompileCounter.get().window()
        before = cm.compiles.value
        for rows in (5, 6, 7, 8, 5, 7):
            out = net.output(rng.normal(size=(rows, 5)).astype(np.float32))
            assert out.shape == (rows, 3)
        assert cm.compiles.value - before == 0
        assert counter.stop() == 0

    def test_f64_input_reuses_f32_executable(self, rng):
        """Satellite regression: host-dtype (f64 under the x64 test env)
        inputs canonicalize at the boundary — same executable, same
        result, zero new compiles."""
        net = _dense_net(seed=23)
        cm = get_compile_manager()
        x32 = rng.normal(size=(4, 5)).astype(np.float32)
        ref = np.asarray(net.output(x32))
        counter = _BackendCompileCounter.get().window()
        before = cm.compiles.value
        out64 = np.asarray(net.output(x32.astype(np.float64)))
        assert cm.compiles.value - before == 0
        assert counter.stop() == 0
        np.testing.assert_array_equal(out64, ref)

    def test_feed_forward_canonicalizes_dtype(self, rng):
        """feed_forward shares the boundary cast: a differently-typed input
        produces activations in the params' compute dtype, identical to the
        compute-dtype call (under the x64 test env that dtype is f64, in
        production f32 — the contract is 'one dtype per model')."""
        net = _dense_net(seed=29)
        compute = np.asarray(net.params[0]["W"]).dtype
        # f32 values are exactly representable in every wider float, so the
        # two calls canonicalize to the same compute-dtype array
        x32 = rng.normal(size=(4, 5)).astype(np.float32)
        acts_c = net.feed_forward(x32.astype(compute))
        acts_o = net.feed_forward(x32)
        assert all(np.asarray(a).dtype == compute for a in acts_o)
        for a, b in zip(acts_c, acts_o):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_time_buckets_bound_program_count(self, rng):
        """Ragged sequence lengths land in O(log T) executables."""
        net = _rnn_net(seed=31)
        cm = get_compile_manager()
        net.output(rng.normal(size=(2, 8, 6)).astype(np.float32))  # bucket 8
        before = cm.compiles.value
        for t in (5, 6, 7, 8):
            net.output(rng.normal(size=(2, t, 6)).astype(np.float32))
        assert cm.compiles.value - before == 0


class TestFusedArgmax:
    def test_predict_transfers_indices_only(self, rng, legacy_env):
        net = _dense_net(seed=37)
        x = rng.normal(size=(6, 5)).astype(np.float32)
        pred = net.predict(x)
        assert pred.dtype == np.int32 and pred.shape == (6,)
        logits = np.asarray(legacy_env(lambda: net.output(x)))
        np.testing.assert_array_equal(pred, logits.argmax(-1))

    def test_predict_recurrent_time_sliced(self, rng, legacy_env):
        net = _rnn_net(seed=41)
        x = rng.normal(size=(2, 5, 6)).astype(np.float32)  # T=5 -> bucket 8
        pred = net.predict(x)
        assert pred.shape == (2, 5)
        logits = np.asarray(legacy_env(lambda: net.output(x)))
        np.testing.assert_array_equal(pred, logits.argmax(-1))

    def test_graph_predict(self, rng, legacy_env):
        net = _graph_net(seed=43)
        x = rng.normal(size=(5, 4)).astype(np.float32)
        pred = net.predict(x)
        logits = np.asarray(legacy_env(lambda: net.output(x)))
        np.testing.assert_array_equal(pred, logits.argmax(-1))


class TestRnnTimeStepContinuity:
    def test_state_continuity_across_bucketed_calls(self, rng, legacy_env):
        """Multi-step (bucketed T) then single-step streaming must carry
        state exactly like the legacy unbucketed stream."""
        net = _rnn_net(seed=47)
        x = rng.normal(size=(3, 7, 6)).astype(np.float32)
        net.rnn_clear_previous_state()
        o1 = np.asarray(net.rnn_time_step(x[:, :3]))  # T=3 -> bucket 4
        o2 = np.asarray(net.rnn_time_step(x[:, 3, :]))  # single step
        o3 = np.asarray(net.rnn_time_step(x[:, 4:]))  # T=3 tail
        twin = MultiLayerNetwork(net.conf).init(params=net.params)

        def legacy_stream():
            twin.rnn_clear_previous_state()
            return [np.asarray(twin.rnn_time_step(x[:, :3])),
                    np.asarray(twin.rnn_time_step(x[:, 3, :])),
                    np.asarray(twin.rnn_time_step(x[:, 4:]))]

        r1, r2, r3 = legacy_env(legacy_stream)
        np.testing.assert_array_equal(o1, r1)
        np.testing.assert_array_equal(o2, r2)
        np.testing.assert_array_equal(o3, r3)

    def test_single_step_program_reuse(self, rng):
        """Token-by-token decode reuses ONE executable."""
        net = _rnn_net(seed=53)
        net.rnn_clear_previous_state()
        cm = get_compile_manager()
        net.rnn_time_step(rng.normal(size=(2, 6)).astype(np.float32))
        before = cm.compiles.value
        for _ in range(5):
            net.rnn_time_step(rng.normal(size=(2, 6)).astype(np.float32))
        assert cm.compiles.value - before == 0


class TestSharedLruTenancy:
    def test_inference_entries_live_in_the_training_cache(self, rng):
        """Inference executables share the process LRU with training
        entries (multi-model tenancy = plain eviction)."""
        net = _dense_net(seed=59)
        cm = get_compile_manager()
        net.output(rng.normal(size=(4, 5)).astype(np.float32))
        kinds = {cm._key_kind(k) for k in cm._entries}
        assert "mln_infer" in kinds
        # retiring the net's generation evicts its inference entries too
        net.init(force=True)
        kinds_after = {
            cm._key_kind(k) for k in cm._entries
            if isinstance(k, tuple) and k and k[0] == net._cm_token}
        assert "mln_infer" not in kinds_after

    def test_legacy_escape_hatch(self, rng, monkeypatch):
        net = _dense_net(seed=61)
        x = rng.normal(size=(3, 5)).astype(np.float32)
        fast = np.asarray(net.output(x))
        monkeypatch.setenv(inf.INFER_ENV, "legacy")
        legacy = net.output(x)
        # legacy returns a device array, same numbers (3 rows vs the padded
        # 4-row bucket: two programs, equal to f32's last ulp or two)
        assert isinstance(legacy, jax.Array)
        np.testing.assert_allclose(fast, np.asarray(legacy), rtol=5e-7,
                                   atol=0)
