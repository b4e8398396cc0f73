"""CheckpointStore (ISSUE 10): versioned, atomic, retention-bounded model
checkpoints with bit-identical restore-and-resume — params, updater
moments, step count AND the training rng key — on both net classes,
including a bf16-storage MeshLayout model.

Bit-exactness note (memory: env quirks): resumed trajectories replay the
SAME program shapes, so the x64 suite's f64 reduction orders match exactly.
"""

import os
import zipfile

import jax
import jax.numpy as jnp
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
from deeplearning4j_tpu.runtime.checkpoint import CheckpointStore
from deeplearning4j_tpu.telemetry import MetricsRegistry


def _conf(seed=7, features=12, hidden=16, classes=3, params_dtype=None,
          dropout=0.0):
    return MultiLayerConfiguration(
        layers=[
            DenseLayer(n_out=hidden, activation="tanh", dropout=dropout),
            OutputLayer(n_out=classes, activation="softmax", loss="mcxent"),
        ],
        input_type=InputType.feed_forward(features),
        updater=UpdaterConfig(updater="adam", learning_rate=1e-2),
        seed=seed,
        params_dtype=params_dtype,
    )


def _graph_conf(seed=5, features=10, classes=3):
    return (ComputationGraphConfiguration.builder()
            .add_inputs("in")
            .add_layer("h", DenseLayer(n_out=12, activation="relu"), "in")
            .add_layer("out", OutputLayer(n_out=classes, activation="softmax",
                                          loss="mcxent"), "h")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(features))
            .build())


def _windows(rng, n, batch=8, features=12, classes=3, k=2):
    xs = rng.normal(size=(n, k, batch, features)).astype(np.float32)
    ys = np.stack([
        np.eye(classes, dtype=np.float32)[rng.integers(0, classes,
                                                       (k, batch))]
        for _ in range(n)])
    return xs, ys


def _leaves_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestStoreMechanics:
    def test_versions_monotonic_and_atomic(self, tmp_path):
        net = MultiLayerNetwork(_conf()).init()
        store = CheckpointStore(str(tmp_path), retain=10,
                                registry=MetricsRegistry())
        infos = [store.save(net) for _ in range(3)]
        assert [i.version for i in infos] == [1, 2, 3]
        # no torn temp files survive a save
        assert all(not f.startswith(".tmp") for f in os.listdir(tmp_path))
        # a fresh store over the same directory resumes the id sequence
        store2 = CheckpointStore(str(tmp_path), registry=MetricsRegistry())
        assert store2.save(net).version == 4

    def test_retention_prunes_oldest_only(self, tmp_path):
        net = MultiLayerNetwork(_conf()).init()
        store = CheckpointStore(str(tmp_path), retain=2,
                                registry=MetricsRegistry())
        for _ in range(5):
            store.save(net)
        versions = [v.version for v in store.versions()]
        assert versions == [4, 5]
        assert store.latest().version == 5

    def test_torn_and_foreign_files_ignored(self, tmp_path):
        net = MultiLayerNetwork(_conf()).init()
        store = CheckpointStore(str(tmp_path), registry=MetricsRegistry())
        store.save(net)
        (tmp_path / "model-v00000099.zip").write_bytes(b"not a zip")
        (tmp_path / "notes.txt").write_text("hi")
        assert [v.version for v in store.versions()] == [1]
        # ...but the id scan still moves past the torn file's number
        assert store.save(net).version == 100

    def test_save_async_join_surfaces_errors(self, tmp_path):
        net = MultiLayerNetwork(_conf()).init()
        store = CheckpointStore(str(tmp_path), registry=MetricsRegistry())
        v = store.save_async(net)
        store.join()
        assert store.latest().version == v
        assert store.versions()[0].model_class == "MultiLayerNetwork"

    def test_restore_missing_version_raises(self, tmp_path):
        store = CheckpointStore(str(tmp_path), registry=MetricsRegistry())
        with pytest.raises(FileNotFoundError):
            store.restore()
        net = MultiLayerNetwork(_conf()).init()
        store.save(net)
        with pytest.raises(FileNotFoundError):
            store.restore(42)


class TestResumeBitIdentical:
    def _run(self, net, xs, ys):
        losses = []
        for i in range(xs.shape[0]):
            losses.append(net.fit_on_device(xs[i], ys[i]))
        return np.concatenate(losses)

    def test_mln_resume_matches_uninterrupted(self, tmp_path):
        rng = np.random.default_rng(0)
        xs, ys = _windows(rng, 6)
        ref = MultiLayerNetwork(_conf()).init()
        live = MultiLayerNetwork(_conf()).init()
        store = CheckpointStore(str(tmp_path), registry=MetricsRegistry())
        ref_losses = self._run(ref, xs, ys)
        self._run(live, xs[:3], ys[:3])
        store.save(live)
        resumed = store.restore()
        assert resumed.iteration == live.iteration
        _leaves_equal(resumed.params, live.params)
        _leaves_equal(resumed.opt_state, live.opt_state)
        np.testing.assert_array_equal(np.asarray(resumed._rng),
                                      np.asarray(live._rng))
        tail = self._run(resumed, xs[3:], ys[3:])
        np.testing.assert_array_equal(tail, ref_losses[len(ref_losses) // 2:])
        _leaves_equal(resumed.params, ref.params)

    def test_mln_resume_with_dropout_rng_chain(self, tmp_path):
        """Dropout draws come from the stored rng key: the resumed chain
        must replay the EXACT masks the uninterrupted run drew."""
        rng = np.random.default_rng(1)
        xs, ys = _windows(rng, 4)
        ref = MultiLayerNetwork(_conf(dropout=0.5)).init()
        live = MultiLayerNetwork(_conf(dropout=0.5)).init()
        store = CheckpointStore(str(tmp_path), registry=MetricsRegistry())
        ref_losses = self._run(ref, xs, ys)
        self._run(live, xs[:2], ys[:2])
        store.save(live)
        resumed = store.restore()
        tail = self._run(resumed, xs[2:], ys[2:])
        np.testing.assert_array_equal(tail, ref_losses[len(ref_losses) // 2:])
        _leaves_equal(resumed.params, ref.params)

    def test_graph_resume_matches_uninterrupted(self, tmp_path):
        rng = np.random.default_rng(2)
        xs, ys = _windows(rng, 6, features=10)
        ref = ComputationGraph(_graph_conf()).init()
        live = ComputationGraph(_graph_conf()).init()
        store = CheckpointStore(str(tmp_path), registry=MetricsRegistry())
        ref_losses = self._run(ref, xs, ys)
        self._run(live, xs[:3], ys[:3])
        store.save(live)
        resumed = store.restore()
        assert isinstance(resumed, ComputationGraph)
        assert resumed.iteration == live.iteration
        _leaves_equal(resumed.opt_state, live.opt_state)
        tail = self._run(resumed, xs[3:], ys[3:])
        np.testing.assert_array_equal(tail, ref_losses[len(ref_losses) // 2:])
        _leaves_equal(resumed.params, ref.params)

    def test_load_into_keeps_executables_warm(self, tmp_path):
        from deeplearning4j_tpu.runtime.compile_manager import (
            get_compile_manager,
        )

        rng = np.random.default_rng(3)
        xs, ys = _windows(rng, 3)
        net = MultiLayerNetwork(_conf()).init()
        store = CheckpointStore(str(tmp_path), registry=MetricsRegistry())
        net.fit_on_device(xs[0], ys[0])
        store.save(net)
        saved_params = jax.tree_util.tree_map(np.asarray, net.params)
        net.fit_on_device(xs[1], ys[1])
        cm = get_compile_manager()
        before = cm.compiles.value
        store.load_into(net)  # rollback in place
        _leaves_equal(net.params, saved_params)
        net.fit_on_device(xs[2], ys[2])  # same shapes: must be a cache hit
        assert cm.compiles.value - before == 0


class TestBf16MeshLayoutRoundtrip:
    def test_bf16_fsdp_model_roundtrips_bit_identical(self, tmp_path):
        from deeplearning4j_tpu.parallel import MeshLayout

        rng = np.random.default_rng(4)
        # hidden/features divisible by fsdp=4 so the kernels actually shard
        net = MultiLayerNetwork(_conf(features=16, hidden=32,
                                      classes=4)).init()
        lo = MeshLayout(data=1, fsdp=4, params_dtype="bfloat16",
                        devices=jax.devices()[:4])
        lo.apply(net)
        xs = rng.normal(size=(2, 8, 16)).astype(np.float32)
        ys = np.eye(4, dtype=np.float32)[rng.integers(0, 4, (2, 8))]
        net.fit_on_device(xs, ys)
        store = CheckpointStore(str(tmp_path), registry=MetricsRegistry())
        store.save(net)

        # fresh-model restore: conf round-trips params_dtype, leaves come
        # back bf16 and bit-identical (bf16 -> f32 widening is lossless)
        restored = store.restore()
        for a, b in zip(jax.tree_util.tree_leaves(restored.params),
                        jax.tree_util.tree_leaves(net.params)):
            assert a.dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32))

        # in-place rollback re-places leaves on the net's layout
        net.fit_on_device(xs, ys)
        store.load_into(net)
        W = net.params[0]["W"]
        assert W.dtype == jnp.bfloat16
        assert "fsdp" in str(W.sharding.spec)
        for a, b in zip(jax.tree_util.tree_leaves(restored.params),
                        jax.tree_util.tree_leaves(net.params)):
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32))
        # and the restored model still trains sharded to a finite loss
        losses = net.fit_on_device(xs, ys)
        assert np.all(np.isfinite(losses))


def test_rng_entry_present_in_container(tmp_path):
    net = MultiLayerNetwork(_conf()).init()
    store = CheckpointStore(str(tmp_path), registry=MetricsRegistry())
    info = store.save(net)
    with zipfile.ZipFile(info.path) as zf:
        names = set(zf.namelist())
    assert {"configuration.json", "coefficients.npz", "updaterState.npz",
            "state.npz", "meta.json", "rng.npz", "manifest.json"} <= names


class TestIntegrityQuarantine:
    """ISSUE 14: sha256 manifest verification, quarantine, and fallback to
    the previous good version on every corruption shape a killed/ill
    writer can leave behind."""

    def _seed(self, tmp_path, n=2):
        net = MultiLayerNetwork(_conf()).init()
        store = CheckpointStore(str(tmp_path), registry=MetricsRegistry())
        infos = [store.save(net) for _ in range(n)]
        return net, store, infos

    def test_verify_ok_and_legacy(self, tmp_path):
        net, store, (i1, i2) = self._seed(tmp_path)
        assert store.verify(1) == "ok"
        # a manifest-less container (pre-manifest era) is accepted as-is
        with zipfile.ZipFile(i2.path) as zf:
            entries = {n: zf.read(n) for n in zf.namelist()
                       if n != "manifest.json"}
        with zipfile.ZipFile(i2.path, "w") as zf:
            for name, data in entries.items():
                zf.writestr(name, data)
        assert store.verify(2) == "legacy"

    @pytest.mark.parametrize("first_seed", [0, 100, 200, 300])
    def test_flipped_bytes_never_pass_as_a_legacy_container(self, tmp_path,
                                                            first_seed):
        """64 flipped bytes that hit the zip's directory can hide the
        manifest's name or cut the entry list short: the container then has
        no manifest, and only a whole one (every entry sound, the model's
        ``meta.json`` among them) may pass as pre-manifest. One seed in
        eight used to, and ``restore`` then failed on the entry it lacked."""
        from deeplearning4j_tpu.runtime.checkpoint import (
            CheckpointCorruptError,
        )
        from deeplearning4j_tpu.testing.chaos import corrupt_file

        net, store, (i1, i2) = self._seed(tmp_path)
        with open(i2.path, "rb") as fh:
            whole = fh.read()
        for seed in range(first_seed, first_seed + 100):
            with open(i2.path, "wb") as fh:
                fh.write(whole)
            corrupt_file(i2.path, seed, n_bytes=64)
            with pytest.raises(CheckpointCorruptError):
                store.verify(2)
        model, info = store.restore_with_info()   # the last one: falls back
        assert info.version == 1
        assert os.path.exists(i2.path + ".quarantine")

    def test_truncated_zip_quarantined_with_fallback(self, tmp_path):
        from deeplearning4j_tpu.testing.chaos import truncate_file

        net, store, (i1, i2) = self._seed(tmp_path)
        truncate_file(i2.path, keep_frac=0.4)
        model, info = store.restore_with_info()
        assert info.version == 1
        assert os.path.exists(i2.path + ".quarantine")
        assert [v.version for v in store.versions()] == [1]
        assert store._m_corrupt.value >= 1

    def test_bad_rng_entry_digest_mismatch(self, tmp_path):
        from deeplearning4j_tpu.runtime.checkpoint import (
            CheckpointCorruptError,
        )

        net, store, (i1, i2) = self._seed(tmp_path)
        # rewrite rng.npz in place; the manifest still carries the old
        # digest, so the zip stays structurally valid but fails verify
        with zipfile.ZipFile(i2.path) as zf:
            entries = {n: zf.read(n) for n in zf.namelist()}
        entries["rng.npz"] = b"\x00" * 32
        with zipfile.ZipFile(i2.path, "w") as zf:
            for name, data in entries.items():
                zf.writestr(name, data)
        with pytest.raises(CheckpointCorruptError, match="rng.npz"):
            store.verify(2)
        model, info = store.restore_with_info()
        assert info.version == 1
        assert os.path.exists(i2.path + ".quarantine")

    def test_manifest_zip_mismatch_quarantined(self, tmp_path):
        from deeplearning4j_tpu.runtime.checkpoint import (
            CheckpointCorruptError,
        )

        net, store, (i1, i2) = self._seed(tmp_path)
        with zipfile.ZipFile(i2.path, "a") as zf:
            zf.writestr("smuggled.bin", b"x")
        with pytest.raises(CheckpointCorruptError, match="mismatch"):
            store.verify(2)
        model, info = store.restore_with_info()
        assert info.version == 1
        assert os.path.exists(i2.path + ".quarantine")

    def test_pinned_corrupt_version_raises_after_quarantine(self, tmp_path):
        from deeplearning4j_tpu.runtime.checkpoint import (
            CheckpointCorruptError,
        )
        from deeplearning4j_tpu.testing.chaos import truncate_file

        net, store, (i1, i2) = self._seed(tmp_path)
        truncate_file(i2.path, keep_frac=0.3)
        # an explicitly pinned version must NOT silently fall back
        with pytest.raises(CheckpointCorruptError):
            store.restore(2)
        assert os.path.exists(i2.path + ".quarantine")
        # ...while the unpinned path still serves the survivor
        assert store.restore_with_info()[1].version == 1

    def test_store_with_no_intact_versions(self, tmp_path):
        from deeplearning4j_tpu.testing.chaos import truncate_file

        net, store, (i1,) = self._seed(tmp_path, n=1)
        truncate_file(i1.path, keep_frac=0.3)
        with pytest.raises(FileNotFoundError, match="no intact versions"):
            store.restore()

    def test_ids_monotonic_past_quarantine(self, tmp_path):
        from deeplearning4j_tpu.testing.chaos import truncate_file

        net, store, (i1, i2) = self._seed(tmp_path)
        truncate_file(i2.path, keep_frac=0.4)
        store.restore()  # quarantines v2, serves v1
        assert store.save(net).version == 3
        # a FRESH store over the directory still counts the quarantined id
        fresh = CheckpointStore(str(tmp_path), registry=MetricsRegistry())
        assert fresh.save(net).version == 4

    def test_stale_tmp_from_dead_writer_swept(self, tmp_path):
        dead_pid = 2**22 + 1  # linux pid_max caps at 2**22: can't be alive
        torn = tmp_path / f".tmp-v00000002-{dead_pid}"
        torn.write_bytes(b"torn write, never completed")
        live = tmp_path / f".tmp-v00000003-{os.getpid()}"
        live.write_bytes(b"in-flight async writer")
        store = CheckpointStore(str(tmp_path), registry=MetricsRegistry())
        names = set(os.listdir(tmp_path))
        assert torn.name not in names
        assert torn.name + ".quarantine" in names
        # a tmp owned by a LIVE pid is someone's in-flight write: untouched
        assert live.name in names
        assert store._m_corrupt.value == 1
        net = MultiLayerNetwork(_conf()).init()
        assert store.save(net).version == 1

    def test_load_into_falls_back_past_corrupt_latest(self, tmp_path):
        from deeplearning4j_tpu.testing.chaos import corrupt_file

        rng = np.random.default_rng(11)
        xs, ys = _windows(rng, 2)
        net = MultiLayerNetwork(_conf()).init()
        store = CheckpointStore(str(tmp_path), registry=MetricsRegistry())
        net.fit_on_device(xs[0], ys[0])
        store.save(net)
        good_params = jax.tree_util.tree_map(np.asarray, net.params)
        net.fit_on_device(xs[1], ys[1])
        info2 = store.save(net)
        corrupt_file(info2.path, seed=3)
        loaded = store.load_into(net, fallback=True)
        assert loaded == 1
        _leaves_equal(net.params, good_params)
        assert os.path.exists(info2.path + ".quarantine")
