"""Opportunistic egress probe: fetch the two egress-gated assets whenever a
mirror is reachable, upgrading the tests gated on them.

- true MNIST IDX archives -> $MNIST_DIR (default ~/.dl4j-tpu/mnist) via the
  checksum-verified ``fetch_mnist`` (reference: base/MnistFetcher.java:39);
  unlocks ``test_lenet_true_mnist_when_available``.
- Keras VGG16 HDF5 weights -> ~/.dl4j-tpu/vgg16_weights.h5 (reference:
  modelimport TrainedModelHelper.java downloads then imports); unlocks
  ``TrainedModels.load`` without a hand-copied archive. Mirror via
  $DL4J_TPU_VGG16_URL.

Always exits 0 with one JSON summary line — a no-egress machine reports
{"mnist": "unreachable", ...} and nothing else changes (the gated tests
keep skipping). Short socket timeouts: a firewalled host fails in seconds,
not at TCP-retry length. Run: ``python scripts/fetch_gated_assets.py``.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

VGG16_URL = (
    "https://github.com/fchollet/deep-learning-models/releases/download/"
    "v0.1/vgg16_weights_tf_dim_ordering_tf_kernels.h5"
)


def try_mnist(timeout_s: float) -> str:
    root = os.environ.get("MNIST_DIR", os.path.expanduser("~/.dl4j-tpu/mnist"))
    existed = os.path.isdir(root)
    before = set(os.listdir(root)) if existed else set()
    try:
        # import inside the guard: even a broken package install must not
        # break the one-JSON-line / exit-0 contract
        from deeplearning4j_tpu.datasets.fetchers import fetch_mnist

        # explicit per-request timeout: fetch_mnist's urlopen calls ignore
        # the socket default
        return f"fetched:{fetch_mnist(timeout_s=timeout_s)}"
    except Exception as e:  # noqa: BLE001 - opportunistic by design
        # a PARTIAL download must not survive: the gated tests check for
        # the archives, and a half-set would corrupt their skip logic
        if os.path.isdir(root):
            for name in set(os.listdir(root)) - before:
                try:
                    os.remove(os.path.join(root, name))
                except OSError:
                    pass
            if not existed and not os.listdir(root):
                os.rmdir(root)
        return f"unreachable ({type(e).__name__})"


def try_vgg16(timeout_s: float) -> str:
    import urllib.request

    dest = os.path.expanduser("~/.dl4j-tpu/vgg16_weights.h5")
    if os.path.exists(dest) and os.path.getsize(dest) > 1 << 20:
        return f"cached:{dest}"
    url = os.environ.get("DL4J_TPU_VGG16_URL", VGG16_URL)
    os.makedirs(os.path.dirname(dest), exist_ok=True)
    tmp = dest + ".part"
    try:
        import hashlib

        hasher = hashlib.sha256()  # hash the stream: no second full read
        with urllib.request.urlopen(url, timeout=timeout_s) as r, \
                open(tmp, "wb") as f:
            while True:
                chunk = r.read(1 << 20)
                if not chunk:
                    break
                hasher.update(chunk)
                f.write(chunk)
        # sanity: HDF5 signature + the same size floor the cache check
        # applies (the real archive is ~528 MB); optionally a pinned digest
        with open(tmp, "rb") as f:
            if f.read(8) != b"\x89HDF\r\n\x1a\n":
                raise ValueError("downloaded file is not HDF5")
        if os.path.getsize(tmp) <= (1 << 20):
            raise ValueError("downloaded file is implausibly small")
        want = os.environ.get("DL4J_TPU_VGG16_SHA256")
        if want and hasher.hexdigest() != want.lower():
            raise ValueError(
                f"checksum mismatch (got {hasher.hexdigest()[:16]}…)")
        os.replace(tmp, dest)
        return f"fetched:{dest}"
    except Exception as e:  # noqa: BLE001
        if os.path.exists(tmp):
            os.remove(tmp)
        return f"unreachable ({type(e).__name__})"


def main() -> int:
    timeout_s = float(os.environ.get("DL4J_TPU_FETCH_TIMEOUT_S", "10"))
    summary = {
        "mnist": try_mnist(timeout_s),
        "vgg16": try_vgg16(timeout_s),
    }
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
