"""Accuracy parity on real (non-synthetic) data + checksum-verified fetcher
(VERDICT round-2 task 5 / missing #4).

The reference proves accuracy end-to-end by downloading MNIST
(base/MnistFetcher.java:39, digest-pinned) and training LeNet to ~99% in its
integration tests. This build has no egress, so the pinned accuracy rows use
the real corpora available in-image: sklearn's bundled UCI handwritten-digits
scans (1,797 genuine 8×8 images) and Fisher's Iris. The same LeNet config
upgrades itself to true MNIST whenever `fetch_mnist` can reach a mirror (or
MNIST_DIR holds the IDX files) — exercised here against a local file:// mirror
with real digest verification.

The pinned numbers live HERE: each test's thresholds are the record of
what the framework reaches on that data.
"""

import gzip
import hashlib
import os
import struct

import numpy as np
import pytest

from deeplearning4j_tpu import (
    DenseLayer,
    InputType,
    MultiLayerConfiguration,
    MultiLayerNetwork,
    OutputLayer,
    UpdaterConfig,
)
from deeplearning4j_tpu.datasets.fetchers import (
    DigitsDataSetIterator,
    IrisDataSetIterator,
    fetch_mnist,
    load_digits_dataset,
    load_mnist,
)
from deeplearning4j_tpu.models.lenet import lenet_mnist_conf


class TestRealDataAccuracy:
    def test_lenet_digits_accuracy_pinned(self):
        """LeNet-style CNN (conv-pool-conv-pool-dense, kernels scaled to the
        8×8 raster) on REAL handwritten digit scans: >= 0.95 held-out accuracy
        in one short run."""
        from deeplearning4j_tpu.nn.layers.convolution import ConvolutionLayer
        from deeplearning4j_tpu.nn.layers.pooling import SubsamplingLayer

        conf = MultiLayerConfiguration(
            layers=[
                ConvolutionLayer(n_out=20, kernel=(3, 3), activation="identity"),
                SubsamplingLayer(pooling_type="max", kernel=(2, 2), stride=(2, 2)),
                ConvolutionLayer(n_out=50, kernel=(2, 2), activation="identity"),
                SubsamplingLayer(pooling_type="max", kernel=(2, 2), stride=(2, 2)),
                DenseLayer(n_out=128, activation="relu"),
                OutputLayer(n_out=10, activation="softmax", loss="mcxent"),
            ],
            input_type=InputType.convolutional(8, 8, 1),
            updater=UpdaterConfig(updater="adam", learning_rate=2e-3),
            seed=5,
        )
        net = MultiLayerNetwork(conf).init()
        # 18 epochs: the 12-epoch budget sat right on the 0.95 pin and
        # fractional numeric drift across jax/backend versions pushed it to
        # 0.947; the longer run clears the pin with margin (0.964 here)
        net.fit(DigitsDataSetIterator(batch=128, train=True), epochs=18)
        ev = net.evaluate(DigitsDataSetIterator(batch=120, train=False, shuffle=False))
        assert ev.accuracy() >= 0.95, ev.stats()

    def test_mlp_iris_accuracy_pinned(self):
        """MLP on real Fisher Iris: >= 0.95 full-set accuracy."""
        conf = MultiLayerConfiguration(
            layers=[DenseLayer(n_out=16, activation="tanh"),
                    OutputLayer(n_out=3, activation="softmax", loss="mcxent")],
            input_type=InputType.feed_forward(4),
            updater=UpdaterConfig(updater="adam", learning_rate=5e-3),
            seed=6,
        )
        net = MultiLayerNetwork(conf).init()
        it = IrisDataSetIterator(batch=50)
        net.fit(it, epochs=200)
        ev = net.evaluate(IrisDataSetIterator(batch=150, shuffle=False))
        assert ev.accuracy() >= 0.95, ev.stats()

    def test_char_rnn_bits_per_char_pinned(self):
        """Stacked GravesLSTM char model (BASELINE config #3 family) on real
        English text via TBPTT: <= 1.8 bits/char after 60 epochs (measured
        1.36; random over the 29-char vocab is 4.86)."""
        from deeplearning4j_tpu.datasets.iterators import DataSet
        from deeplearning4j_tpu.models.char_rnn import char_rnn

        text = (
            "the quick brown fox jumps over the lazy dog. "
            "pack my box with five dozen liquor jugs. "
            "how vexingly quick daft zebras jump! "
        ) * 8
        vocab = sorted(set(text))
        stoi = {c: i for i, c in enumerate(vocab)}
        ids = np.array([stoi[c] for c in text])
        conf = char_rnn(vocab_size=len(vocab), hidden_size=96, num_layers=2,
                        tbptt_length=32, learning_rate=3e-3, seed=5)
        net = MultiLayerNetwork(conf).init()
        t, b = 64, 8
        n = (len(ids) - 1) // t
        eye = np.eye(len(vocab), dtype=np.float32)
        xs = np.stack([eye[ids[i * t:(i + 1) * t]] for i in range(n)])
        ys = np.stack([eye[ids[i * t + 1:(i + 1) * t + 1]] for i in range(n)])
        for _ in range(60):
            for s in range(0, n - b + 1, b):
                net.fit(DataSet(xs[s:s + b], ys[s:s + b]))
        bpc = float(net.score(DataSet(xs[:b], ys[:b]))) / np.log(2)
        assert bpc <= 1.8, bpc

    def test_digits_corpus_is_real(self):
        x, y = load_digits_dataset()
        assert x.shape == (1797, 64)
        assert set(np.unique(y)) == set(range(10))
        # real scans: non-trivial per-class variance, values quantized to /16
        assert len(np.unique(x)) == 17

    @staticmethod
    def _mnist_present() -> bool:
        """ALL FOUR splits present (the test loads train AND t10k), in any
        layout load_mnist accepts: .gz archives from fetch_mnist, or
        hand-copied decompressed IDX in dash ("train-images-idx3-ubyte") or
        dot ("train-images.idx3-ubyte") naming. Checking files rather than
        the directory: a failed or PARTIAL opportunistic fetch
        (scripts/fetch_gated_assets.py) must not un-skip the test onto
        synthetic fallback data for either split."""
        root = os.environ.get("MNIST_DIR",
                              os.path.expanduser("~/.dl4j-tpu/mnist"))

        def found(split, kind, code):
            names = (f"{split}-{kind}-{code}-ubyte.gz",
                     f"{split}-{kind}-{code}-ubyte",
                     f"{split}-{kind}.{code}-ubyte")
            return any(os.path.exists(os.path.join(root, n)) for n in names)

        return all(found(s, k, c) for s in ("train", "t10k")
                   for k, c in (("images", "idx3"), ("labels", "idx1")))

    @pytest.mark.skipif(
        not _mnist_present.__func__(),
        reason="real MNIST IDX files not present (no egress)",
    )
    def test_lenet_true_mnist_when_available(self):
        """Self-upgrading test (VERDICT task 5): with real MNIST present the
        same config trains on it — LeNet >= 0.97 on a 10k/2k subset."""
        x, y = load_mnist(train=True)
        assert x.shape[1] == 784 and x.shape[0] >= 60000  # real, not synthetic
        from deeplearning4j_tpu.datasets.iterators import NumpyDataSetIterator

        conf = lenet_mnist_conf(learning_rate=1e-3, seed=5)
        net = MultiLayerNetwork(conf).init()
        labels = np.eye(10, dtype=np.float32)[y[:10000]]
        net.fit(NumpyDataSetIterator(x[:10000], labels, 128, shuffle=True, seed=0),
                epochs=3)
        xt, yt = load_mnist(train=False)
        ev = net.evaluate(
            NumpyDataSetIterator(xt[:2000], np.eye(10, dtype=np.float32)[yt[:2000]],
                                 200, shuffle=False))
        assert ev.accuracy() >= 0.97, ev.stats()


def _idx_gz(path: str, arr: np.ndarray) -> None:
    dims = struct.pack(">" + "I" * arr.ndim, *arr.shape)
    with gzip.open(path, "wb") as f:
        f.write(struct.pack(">HBB", 0, 0x08, arr.ndim) + dims +
                arr.astype(">u1").tobytes())


class TestMnistFetcher:
    """MnistFetcher.java:39 parity: download + digest verify, via file://."""

    def _mirror(self, tmp_path, tamper: bool = False):
        mirror = tmp_path / "mirror"
        mirror.mkdir()
        rng = np.random.default_rng(0)
        files = {
            "train-images-idx3-ubyte.gz": rng.integers(0, 255, (12, 28, 28)),
            "train-labels-idx1-ubyte.gz": rng.integers(0, 9, (12,)),
            "t10k-images-idx3-ubyte.gz": rng.integers(0, 255, (4, 28, 28)),
            "t10k-labels-idx1-ubyte.gz": rng.integers(0, 9, (4,)),
        }
        sums = {}
        for name, arr in files.items():
            p = mirror / name
            _idx_gz(str(p), arr.astype(np.uint8))
            sums[name] = hashlib.sha256(p.read_bytes()).hexdigest()
        if tamper:
            name = "train-images-idx3-ubyte.gz"
            (mirror / name).write_bytes(b"corrupted" + (mirror / name).read_bytes())
        return f"file://{mirror}", sums

    def test_fetch_verify_and_load(self, tmp_path):
        url, sums = self._mirror(tmp_path)
        root = str(tmp_path / "data")
        fetch_mnist(root=root, base_url=url, checksums=sums)
        x, y = load_mnist(train=True, root=root)
        assert x.shape == (12, 784) and y.shape == (12,)
        assert x.max() <= 1.0
        # second fetch is a cache hit (mirror can disappear)
        for f in (tmp_path / "mirror").iterdir():
            f.unlink()
        fetch_mnist(root=root, base_url=url, checksums=sums)

    def test_fetch_rejects_tampered_file(self, tmp_path):
        url, sums = self._mirror(tmp_path, tamper=True)
        root = str(tmp_path / "data")
        with pytest.raises(ValueError, match="checksum mismatch"):
            fetch_mnist(root=root, base_url=url, checksums=sums)
        assert not os.path.exists(os.path.join(root, "train-images-idx3-ubyte.gz"))

    def test_pinned_digests_present(self):
        from deeplearning4j_tpu.datasets.fetchers import MNIST_SHA256

        assert len(MNIST_SHA256) == 4
        assert all(len(v) == 64 for v in MNIST_SHA256.values())
