"""DataSet + iterator framework with async prefetch.

TPU-native equivalent of the reference's dataset tier (SURVEY.md §2.1 "Dataset
iterator framework"): ND4J ``DataSet``/``DataSetIterator`` +
``AsyncDataSetIterator`` (deeplearning4j-nn/.../datasets/iterator/
AsyncDataSetIterator.java:36 — bounded queue + consumer thread, auto-inserted by
fit at MultiLayerNetwork.java:920-924), plus the composition utilities
(MultipleEpochsIterator, SamplingDataSetIterator, ExistingDataSetIterator,
IteratorDataSetIterator, INDArrayDataSetIterator, ListDataSetIterator).

Host-side by design: iterators produce numpy batches; the device boundary is
crossed once per step inside the jitted train step (or explicitly via sharding
in the parallel trainer). Static batch shapes are the contract — the final
short batch can be dropped or padded so XLA never sees a new shape.
"""

from __future__ import annotations

import queue
import threading
from dataclasses import dataclass, field
from typing import Iterable, Iterator, List, Optional, Sequence

import numpy as np


@dataclass
class DataSet:
    """Features+labels (+masks) minibatch (reference: ND4J DataSet)."""

    features: np.ndarray
    labels: np.ndarray
    features_mask: Optional[np.ndarray] = None
    labels_mask: Optional[np.ndarray] = None
    # per-example provenance (reference: DataSet.getExampleMetaData — carried
    # from RecordReader iterators into Evaluation's Prediction records)
    example_metadata: Optional[List] = None

    def num_examples(self) -> int:
        return int(self.features.shape[0])

    def split_test_and_train(self, n_train: int):
        def take(sl):
            return DataSet(
                self.features[sl],
                self.labels[sl],
                None if self.features_mask is None else self.features_mask[sl],
                None if self.labels_mask is None else self.labels_mask[sl],
                None if self.example_metadata is None else self.example_metadata[sl],
            )

        return take(slice(None, n_train)), take(slice(n_train, None))

    def shuffle(self, seed: int = 0) -> "DataSet":
        rng = np.random.default_rng(seed)
        idx = rng.permutation(self.num_examples())
        return DataSet(
            self.features[idx],
            self.labels[idx],
            None if self.features_mask is None else self.features_mask[idx],
            None if self.labels_mask is None else self.labels_mask[idx],
            None if self.example_metadata is None
            else [self.example_metadata[i] for i in idx],
        )


@dataclass
class MultiDataSet:
    """Multi-input/multi-output batch (reference: ND4J MultiDataSet), for ComputationGraph."""

    features: List[np.ndarray]
    labels: List[np.ndarray]
    features_masks: Optional[List[Optional[np.ndarray]]] = None
    labels_masks: Optional[List[Optional[np.ndarray]]] = None
    # per-example provenance, shared across outputs (reference:
    # MultiDataSet.getExampleMetaData)
    example_metadata: Optional[List] = None

    def num_examples(self) -> int:
        return int(self.features[0].shape[0])


class DataSetIterator:
    """Base iterator (reference: DataSetIterator interface). Iterable + reset."""

    prefetch_supported = True

    def reset(self) -> None:
        pass

    def __iter__(self) -> Iterator[DataSet]:
        raise NotImplementedError

    def batch_size(self) -> int:
        raise NotImplementedError


class ListDataSetIterator(DataSetIterator):
    """Iterate a list of pre-built DataSets (reference: ListDataSetIterator)."""

    def __init__(self, datasets: Sequence[DataSet]):
        self._data = list(datasets)

    def __iter__(self):
        return iter(self._data)

    def batch_size(self):
        return self._data[0].num_examples() if self._data else 0

    def __len__(self):
        return len(self._data)


class NumpyDataSetIterator(DataSetIterator):
    """Batch up (features, labels) arrays (reference: INDArrayDataSetIterator).

    ``drop_last`` keeps batch shapes static for XLA (a trailing short batch
    would trigger a recompile).
    """

    def __init__(self, features, labels, batch: int, drop_last: bool = True,
                 shuffle: bool = False, seed: int = 0):
        self.features = np.asarray(features)
        self.labels = np.asarray(labels)
        self.batch = int(batch)
        self.drop_last = drop_last
        self.shuffle = shuffle
        self.seed = seed
        self._epoch = 0

    def batch_size(self):
        return self.batch

    def __iter__(self):
        n = self.features.shape[0]
        idx = np.arange(n)
        if self.shuffle:
            rng = np.random.default_rng(self.seed + self._epoch)
            rng.shuffle(idx)
        self._epoch += 1
        stop = n - (n % self.batch) if self.drop_last else n
        for s in range(0, stop, self.batch):
            sl = idx[s : s + self.batch]
            yield DataSet(self.features[sl], self.labels[sl])

    def __len__(self):
        n = self.features.shape[0]
        return n // self.batch if self.drop_last else -(-n // self.batch)


class ExistingDataSetIterator(DataSetIterator):
    """Wrap any python iterable of DataSets (reference: ExistingDataSetIterator)."""

    def __init__(self, iterable: Iterable[DataSet]):
        self._iterable = iterable

    def __iter__(self):
        return iter(self._iterable)

    def batch_size(self):
        return 0


class MultipleEpochsIterator(DataSetIterator):
    """Replay an iterator N times (reference: MultipleEpochsIterator)."""

    def __init__(self, epochs: int, base: DataSetIterator):
        self.epochs = epochs
        self.base = base

    def __iter__(self):
        for _ in range(self.epochs):
            self.base.reset()
            yield from self.base

    def batch_size(self):
        return self.base.batch_size()


class SamplingDataSetIterator(DataSetIterator):
    """Sample random minibatches with replacement (reference: SamplingDataSetIterator)."""

    def __init__(self, dataset: DataSet, batch: int, total_batches: int, seed: int = 0):
        self.dataset = dataset
        self.batch = batch
        self.total = total_batches
        self.seed = seed

    def batch_size(self):
        return self.batch

    def __iter__(self):
        rng = np.random.default_rng(self.seed)
        n = self.dataset.num_examples()
        for _ in range(self.total):
            idx = rng.integers(0, n, size=self.batch)
            yield DataSet(self.dataset.features[idx], self.dataset.labels[idx])


class IteratorDataSetIterator(DataSetIterator):
    """Re-batch a stream of single examples (reference: IteratorDataSetIterator)."""

    def __init__(self, examples: Iterable[DataSet], batch: int):
        self.examples = examples
        self.batch = batch

    def batch_size(self):
        return self.batch

    def __iter__(self):
        feats, labs, metas = [], [], []
        for ex in self.examples:
            feats.append(ex.features)
            labs.append(ex.labels)
            if ex.example_metadata:
                metas.extend(ex.example_metadata)
            if len(feats) == self.batch:
                yield DataSet(np.stack(feats), np.stack(labs),
                              example_metadata=metas if len(metas) == len(feats) else None)
                feats, labs, metas = [], [], []


class ReconstructionDataSetIterator(DataSetIterator):
    """Labels = features, for autoencoder/pretrain targets
    (reference: ReconstructionDataSetIterator.java)."""

    def __init__(self, base: DataSetIterator):
        self.base = base

    def batch_size(self):
        return self.base.batch_size()

    def reset(self):
        self.base.reset()

    def __iter__(self):
        for ds in self.base:
            yield DataSet(ds.features, ds.features,
                          features_mask=ds.features_mask,
                          labels_mask=ds.features_mask,
                          example_metadata=ds.example_metadata)


class IteratorMultiDataSetIterator(DataSetIterator):
    """Re-batch a stream of MultiDataSets into EXACT ``batch``-sized batches
    (reference: IteratorMultiDataSetIterator.java — the overflowing source
    batch is split and the remainder queued). Only the trailing batch may be
    short; everything else honors the static-batch-shape contract. Mixed
    mask presence merges like the reference's MultiDataSet.merge: unmasked
    members contribute all-ones masks."""

    def __init__(self, examples: Iterable[MultiDataSet], batch: int):
        if int(batch) < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        self.examples = examples
        self.batch = int(batch)

    def batch_size(self):
        return self.batch

    def __iter__(self):
        buf: List[MultiDataSet] = []
        count = 0

        def cat_masks(buf, kind, n):
            """Concat per-position masks across buffered sets; members
            without a mask get all-ones of the masked members' shape."""
            mask_lists = [getattr(m, kind) for m in buf]
            if all(ml is None for ml in mask_lists):
                return None
            out = []
            for i in range(n):
                col = [None if ml is None else ml[i] for ml in mask_lists]
                if all(m is None for m in col):
                    out.append(None)
                    continue
                trailing = next(np.asarray(m).shape[1:] for m in col
                                if m is not None)
                parts = []
                for m, mds in zip(col, buf):
                    parts.append(
                        np.ones((mds.num_examples(),) + trailing, np.float32)
                        if m is None else np.asarray(m)
                    )
                out.append(np.concatenate(parts))
            return out

        def concat_all(buf):
            n_in = len(buf[0].features)
            n_out = len(buf[0].labels)
            metas = None
            if any(m.example_metadata for m in buf):
                metas = []
                for m in buf:
                    metas.extend(m.example_metadata or
                                 [None] * m.num_examples())
            return MultiDataSet(
                features=[np.concatenate([np.asarray(m.features[i]) for m in buf])
                          for i in range(n_in)],
                labels=[np.concatenate([np.asarray(m.labels[i]) for m in buf])
                        for i in range(n_out)],
                features_masks=cat_masks(buf, "features_masks", n_in),
                labels_masks=cat_masks(buf, "labels_masks", n_out),
                example_metadata=metas,
            )

        def take(mds, sl):
            """Row-slice every array (and metadata) of a MultiDataSet."""
            return MultiDataSet(
                features=[f[sl] for f in mds.features],
                labels=[l[sl] for l in mds.labels],
                features_masks=None if mds.features_masks is None
                else [None if m is None else m[sl] for m in mds.features_masks],
                labels_masks=None if mds.labels_masks is None
                else [None if m is None else m[sl] for m in mds.labels_masks],
                example_metadata=None if mds.example_metadata is None
                else mds.example_metadata[sl],
            )

        for mds in self.examples:
            buf.append(mds)
            count += mds.num_examples()
            if count >= self.batch:
                # merge ONCE per buffer fill, then yield successive slices
                # (numpy row-slices are views) — re-concatenating the
                # shrinking remainder each split would be O(N^2/batch)
                merged = concat_all(buf)
                k = 0
                while count - k >= self.batch:
                    yield take(merged, slice(k, k + self.batch))
                    k += self.batch
                buf = [take(merged, slice(k, None))] if count - k else []
                count -= k
        if buf:
            yield concat_all(buf)


_SENTINEL = object()


class AsyncDataSetIterator(DataSetIterator):
    """Background-thread prefetch with a bounded queue.

    Reference: AsyncDataSetIterator.java:36 (consumer thread started at :79,
    queue capacity default 8). Overlaps host-side batch prep with device
    compute — the HBM-feeding side of the input pipeline.
    """

    prefetch_supported = False  # already async; never double-wrap

    def __init__(self, base: DataSetIterator, queue_size: int = 8):
        self.base = base
        self.queue_size = queue_size

    def batch_size(self):
        return self.base.batch_size()

    def reset(self):
        self.base.reset()

    def __iter__(self):
        # the producer-thread/sentinel/drain machinery (and its counters)
        # lives once, in utils.collections.AsyncIterator (the generic
        # reference sibling)
        from ..utils.collections import AsyncIterator  # noqa: PLC0415

        yield from AsyncIterator(self.base, queue_size=self.queue_size)


class AsyncMultiDataSetIterator(AsyncDataSetIterator):
    """MultiDataSet flavor (reference: AsyncMultiDataSetIterator.java). The
    prefetch pump is payload-agnostic, so this is the same machinery under
    the reference's multi-input name."""


class DevicePrefetchIterator(DataSetIterator):
    """Stage each batch on device ONE step ahead of consumption.

    ``jax.device_put`` is asynchronous, so staging batch i+1 while the
    consumer computes on batch i overlaps the host→HBM transfer with device
    compute — the device-side complement of AsyncDataSetIterator's host-side
    prefetch (together they form the reference's AsyncDataSetIterator +
    GridExecutioner pipeline, SURVEY.md §2.9, TPU-style).
    """

    prefetch_supported = False  # device staging subsumes host prefetch wrapping

    def __init__(self, base: DataSetIterator, device=None):
        self.base = base
        self.device = device

    def batch_size(self):
        return self.base.batch_size()

    def reset(self):
        self.base.reset()

    def _stage(self, ds):
        import jax  # noqa: PLC0415

        put = (lambda a: jax.device_put(a, self.device)) if self.device else jax.device_put

        def opt(a):
            return None if a is None else put(a)

        if isinstance(ds, MultiDataSet):
            return MultiDataSet(
                [put(f) for f in ds.features],
                [put(l) for l in ds.labels],
                None if ds.features_masks is None else [opt(m) for m in ds.features_masks],
                None if ds.labels_masks is None else [opt(m) for m in ds.labels_masks],
            )
        return DataSet(
            put(ds.features), put(ds.labels),
            opt(ds.features_mask), opt(ds.labels_mask),
        )

    def __iter__(self):
        prev = None
        for ds in self.base:
            staged = self._stage(ds)  # async: overlaps with compute on `prev`
            if prev is not None:
                yield prev
            prev = staged
        if prev is not None:
            yield prev


def as_iterator(data) -> Iterable[DataSet]:
    """Normalize fit() input: (x, y) tuple, DataSet, MultiDataSet, or iterator."""
    if isinstance(data, (DataSet, MultiDataSet)):
        return ListDataSetIterator([data])
    if isinstance(data, tuple) and len(data) == 2:
        return ListDataSetIterator([DataSet(np.asarray(data[0]), np.asarray(data[1]))])
    return data


def pad_to_bucket(x, boundaries: Sequence[int]):
    """Pad a [B, T, F] (or [T, F]) sequence batch to the smallest bucket
    boundary >= T. Returns ``(padded, mask, t)`` where ``mask`` is the
    [B, bound] (or [bound]) features mask and ``t`` the real length — slice
    model output with ``out[..., :t, :]``. The streaming companion of
    :class:`BucketingSequenceIterator`: pass both to ``rnn_time_step`` so a
    variable-length stream compiles at most ``len(boundaries)`` programs and
    masked steps hold the recurrent state."""
    x = np.asarray(x)
    t_axis = x.ndim - 2
    t = x.shape[t_axis]
    bound = next((b for b in sorted(int(b) for b in boundaries) if t <= b),
                 None)
    if bound is None:
        raise ValueError(
            f"sequence length {t} exceeds the largest bucket "
            f"{max(boundaries)}; add a larger boundary or truncate"
        )
    pad = [(0, 0)] * x.ndim
    pad[t_axis] = (0, bound - t)
    padded = np.pad(x, pad)
    mask_shape = x.shape[:t_axis] + (bound,)
    mask = np.zeros(mask_shape, dtype=np.float32)
    mask[..., :t] = 1.0
    return padded, mask, t


class BucketingSequenceIterator(DataSetIterator):
    """Group variable-length sequences into a FIXED set of padded lengths.

    SURVEY.md §7 hard part (f): XLA compiles one program per input shape, so
    naive pad-to-longest-in-batch yields as many recompiles as there are
    distinct batch maxima. This iterator assigns every sequence to the
    smallest bucket boundary >= its length, pads (with a features mask — and
    a labels mask for per-step labels) to that boundary, and emits batches
    drawn from ONE bucket at a time — the whole epoch then compiles at most
    ``len(boundaries)`` programs regardless of the length distribution.

    ``sequences``: iterable of (features [T, F], labels [T, C] per-step or
    [C] per-sequence) pairs. Overlong sequences go to the largest bucket
    truncated (reference analog: the truncation semantics of TBPTT windows).
    """

    def __init__(self, sequences, batch: int,
                 boundaries: Sequence[int] = (32, 64, 128, 256),
                 drop_remainder: bool = False):
        self.sequences = list(sequences)
        self.batch = int(batch)
        self.boundaries = sorted(int(b) for b in boundaries)
        if not self.boundaries:
            raise ValueError("need at least one bucket boundary")
        self.drop_remainder = drop_remainder

    def batch_size(self):
        return self.batch

    def _bucket_of(self, length: int) -> int:
        for b in self.boundaries:
            if length <= b:
                return b
        return self.boundaries[-1]  # overlong: truncate into the last bucket

    def _pad(self, feats, labels, bound: int):
        f = np.asarray(feats, dtype=np.float32)[:bound]
        t = f.shape[0]
        fp = np.zeros((bound,) + f.shape[1:], dtype=np.float32)
        fp[:t] = f
        fmask = np.zeros(bound, dtype=np.float32)
        fmask[:t] = 1.0
        l = np.asarray(labels, dtype=np.float32)
        if l.ndim == 2:  # per-step labels pad + mask alongside
            l = l[:bound]
            lp = np.zeros((bound,) + l.shape[1:], dtype=np.float32)
            lp[: l.shape[0]] = l
            lmask = np.zeros(bound, dtype=np.float32)
            lmask[: l.shape[0]] = 1.0
            return fp, fmask, lp, lmask
        return fp, fmask, l, None

    def __iter__(self):
        buckets: dict = {}
        for feats, labels in self.sequences:
            bound = self._bucket_of(np.asarray(feats).shape[0])
            buckets.setdefault(bound, []).append((feats, labels))
        for bound in self.boundaries:
            items = buckets.get(bound, [])
            for s in range(0, len(items), self.batch):
                chunk = items[s : s + self.batch]
                if self.drop_remainder and len(chunk) < self.batch:
                    continue
                padded = [self._pad(f, l, bound) for f, l in chunk]
                fs = np.stack([p[0] for p in padded])
                fm = np.stack([p[1] for p in padded])
                ls = np.stack([p[2] for p in padded])
                lm = (np.stack([p[3] for p in padded])
                      if padded[0][3] is not None else None)
                yield DataSet(fs, ls, fm, lm)

    def num_programs(self) -> int:
        """Upper bound on XLA compilations this iterator can cause."""
        lens = {self._bucket_of(np.asarray(f).shape[0]) for f, _ in self.sequences}
        full = len(lens)
        if not self.drop_remainder:
            # trailing partial batches add at most one extra shape per bucket
            full += sum(
                1 for b in lens
                if len([1 for f, _ in self.sequences
                        if self._bucket_of(np.asarray(f).shape[0]) == b]) % self.batch
            )
        return full
