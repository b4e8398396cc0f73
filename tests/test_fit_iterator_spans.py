"""Spans and counters inside ``fit(iterator)`` (ISSUE 38): one
``dl4j.fit.epoch`` a epoch on the calling thread, under it the iterator waits
(``next_batch``), a staged window's ``stack`` / ``put`` / ``dispatch`` or a
batch's ``step`` / ``listeners``, every span of one window (one batch)
carrying its ``window`` (``batch``); the counters at the same boundaries; and
not a bit of what ``fit`` computes moved (the parent's hashes, from
``python tests/fit_iterator_scenarios.py`` on commit 822f25f)."""

import threading

import numpy as np
import pytest

import fit_iterator_scenarios as sc
from deeplearning4j_tpu.datasets.bucketing import BucketedStager
from deeplearning4j_tpu.datasets.iterators import (AsyncDataSetIterator,
                                                   ListDataSetIterator)
from deeplearning4j_tpu.telemetry import (get_recorder, get_registry,
                                          identified, span)
from deeplearning4j_tpu.utils.collections import AsyncIterator

PARENT_HASHES = {
    ("staged", "mln"): "9a9c63c97a89fce5fac2949ec391aa1d6c85ab9cba3a3f237c05c5d8efc0cc5c",
    ("staged", "graph"): "9a9c63c97a89fce5fac2949ec391aa1d6c85ab9cba3a3f237c05c5d8efc0cc5c",
    ("per_batch", "mln"): "9a9c63c97a89fce5fac2949ec391aa1d6c85ab9cba3a3f237c05c5d8efc0cc5c",
    ("per_batch", "graph"): "9a9c63c97a89fce5fac2949ec391aa1d6c85ab9cba3a3f237c05c5d8efc0cc5c",
    ("tbptt", "mln"): "2d2e53c46380f769d750dbcc497b849354aca8fd3a149b5d25ea7c43c8bbd7d2",
    ("tbptt", "graph"): "2d2e53c46380f769d750dbcc497b849354aca8fd3a149b5d25ea7c43c8bbd7d2",
}
DISPATCH_CHILDREN = ["dl4j.fit.prepare", "dl4j.fit.launch", "dl4j.fit.fetch",
                     "dl4j.fit.listeners"]
BATCH_BYTES = sc.ROWS * (sc.FEATURES + sc.CLASSES) * 4  # float32 x and y


def counted() -> dict:
    """``{(family, label value): value, or count of a histogram}``."""
    out = {}
    snap = get_registry().snapshot()
    for name in ("dl4jtpu_iterator_gets_total", "dl4jtpu_fit_host_bytes_total",
                 "dl4jtpu_iterator_produce_seconds",
                 "dl4jtpu_iterator_queue_full_seconds"):
        for row in snap.get(name, {"values": []})["values"]:
            label = next(iter(row["labels"].values()), "")
            out[name, label] = row["value"] if "value" in row else row["count"]
    return out


def added(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()}


class Heard:
    """A listener that keeps the iterations it was told of."""

    supports_staged = True  # replayed after the window's scan

    def __init__(self):
        self.iterations = []

    def iteration_done(self, net, iteration, loss):
        self.iterations.append(iteration)


def fit_and_events(mode, kind, listeners=()):
    """``fit`` over the scenario's stream; the net, the ``dl4j.fit.*`` events
    it left (in closing order) and what the counters moved by. No other
    thread of the test process fits meanwhile."""
    mark, before = len(get_recorder().events), counted()
    net = sc.fit(mode, kind, listeners)
    events = [e for e in get_recorder().events[mark:]
              if e["name"].startswith("dl4j.fit.")]
    return net, events, added(counted(), before)


@pytest.mark.parametrize("kind", sc.KINDS)
@pytest.mark.parametrize("mode", sc.MODES)
def test_fit_over_an_iterator_leaves_its_spans_counters_and_the_parents_bits(
        mode, kind):
    heard = Heard()
    net, events, counts = fit_and_events(mode, kind, [heard])
    staged = mode == "staged"
    segments = -(-sc.STEPS // sc.TBPTT_FWD) if mode == "tbptt" else 1
    assert net.iteration == sc.BATCHES * segments
    assert heard.iterations == list(range(1, net.iteration + 1))

    # one root, closed last, on one thread with everything under it
    epoch = events[-1]
    assert epoch["name"] == "dl4j.fit.epoch"
    assert epoch["args"]["parent"] is None
    assert (epoch["args"]["net"], epoch["args"]["epoch"]) == (kind, 0)
    assert epoch["args"]["stage"] == (sc.STAGE if staged else 0)
    assert epoch["args"]["batches"] == sc.BATCHES
    assert epoch["args"]["windows"] == (3 if staged else 0)
    assert {e["tid"] for e in events} == {threading.get_ident()}
    for e in events[:-1]:
        assert e["args"]["dispatch"] == epoch["args"]["dispatch"], e
        assert e["args"]["parent"] == (
            "dl4j.fit.dispatch" if staged and e["name"] in DISPATCH_CHILDREN
            else "dl4j.fit.epoch"), e
        assert epoch["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= epoch["ts"] + epoch["dur"]

    # every wait is a span: one a batch and the one that ends the stream
    waits = [e for e in events if e["name"] == "dl4j.fit.next_batch"]
    assert [e["args"]["batch"] for e in waits] == list(range(sc.BATCHES + 1))
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)

    if staged:
        # batch b fills window b // STAGE; the last wait finds the stream over
        assert [e["args"]["window"] for e in waits] == [
            min(b // sc.STAGE, 2) for b in range(sc.BATCHES + 1)]
        for name in ["dl4j.fit.stack", "dl4j.fit.put", "dl4j.fit.dispatch",
                     *DISPATCH_CHILDREN]:
            assert [e["args"]["window"] for e in by_name[name]] == [0, 1, 2], name
        assert "dl4j.fit.step" not in by_name
        stacks, puts = by_name["dl4j.fit.stack"], by_name["dl4j.fit.put"]
        assert [e["args"]["batches"] for e in stacks] == [4, 4, 3]
        assert all(e["args"]["padded_rows"] == 0 for e in stacks)
        # the tail of 3 is padded to 4 slots: a window is 4 batches' bytes
        assert [e["args"]["bytes"] for e in stacks + puts] \
            == [sc.STAGE * BATCH_BYTES] * 6
        assert [e["args"]["steps"] for e in by_name["dl4j.fit.dispatch"]] \
            == [4, 4, 3]
        # window i+1 is stacked and put before window i is dispatched
        order = [(e["name"].rsplit(".", 1)[1], e["args"]["window"])
                 for e in sorted(events, key=lambda e: e["ts"])
                 if e["name"] in ("dl4j.fit.stack", "dl4j.fit.put",
                                  "dl4j.fit.dispatch")]
        assert order == [("stack", 0), ("put", 0), ("stack", 1), ("put", 1),
                         ("dispatch", 0), ("stack", 2), ("put", 2),
                         ("dispatch", 1), ("dispatch", 2)]
        path, other, handed = "staged", "per_batch", 3 * sc.STAGE * BATCH_BYTES
    else:
        assert all("window" not in e["args"] for e in events)
        for name in ("dl4j.fit.stack", "dl4j.fit.put", "dl4j.fit.dispatch"):
            assert name not in by_name
        steps, told = by_name["dl4j.fit.step"], by_name["dl4j.fit.listeners"]
        assert [e["args"]["batch"] for e in steps] \
            == [e["args"]["batch"] for e in told] \
            == [b for b in range(sc.BATCHES) for _ in range(segments)]
        if mode == "tbptt":
            assert [e["args"]["segment"] for e in steps] \
                == list(range(segments)) * sc.BATCHES
            per_step = sc.ROWS * sc.TBPTT_FWD * (sc.FEATURES + sc.CLASSES) * 4
        else:
            per_step = BATCH_BYTES
        assert all(e["args"]["bytes"] == per_step for e in steps)
        path, other, handed = "per_batch", "staged", len(steps) * per_step

    # counters add up: gets = batches + the end sentinel, and so on
    gets = (counts["dl4jtpu_iterator_gets_total", "ready"]
            + counts["dl4jtpu_iterator_gets_total", "empty"])
    assert gets == sc.BATCHES + 1
    assert counts["dl4jtpu_iterator_produce_seconds", ""] == sc.BATCHES
    assert counts["dl4jtpu_fit_host_bytes_total", path] == handed
    assert counts.get(("dl4jtpu_fit_host_bytes_total", other), 0) == 0

    assert sc.params_hash(net) == PARENT_HASHES[mode, kind]


def test_a_short_batch_is_padded_rows_of_its_windows_stack_span():
    ds = sc.batches("staged")[:3]
    ds[1].features, ds[1].labels = ds[1].features[:5], ds[1].labels[:5]
    stager = BucketedStager(4)
    mark = len(get_recorder().events)
    ((kind, window),) = stager.plan(
        ds, lambda d: ([d.features], [d.labels], [None], [None]))
    (event,) = [e for e in get_recorder().events[mark:]
                if e["name"] == "dl4j.fit.stack"]
    assert kind == "window" and window.ordinal == 0
    assert event["args"]["window"] == 0 and event["args"]["batches"] == 3
    assert event["args"]["padded_rows"] == sc.ROWS - 5
    # 3 real batches in 4 slots, and a labels mask of [4, ROWS] float32
    assert event["args"]["bytes"] == window.nbytes() \
        == 4 * BATCH_BYTES + 4 * sc.ROWS * 4
    # the next window of the same stager is the next ordinal
    ((_, second),) = stager.plan(
        ds[:1], lambda d: ([d.features], [d.labels], [None], [None]))
    assert second.ordinal == 1


def test_identified_names_the_spans_opened_inside_it_and_only_those():
    mark = len(get_recorder().events)
    with identified(window=3):
        with span("dl4j.test.outer", window=9):   # a span's own arg wins
            with identified(batch=5), span("dl4j.test.inner"):
                pass
        with identified(window=4), span("dl4j.test.second"):
            pass
        with span("dl4j.test.third"):
            pass
    with span("dl4j.test.after"):
        pass
    got = {e["name"].rsplit(".", 1)[1]:
           {k: e["args"].get(k) for k in ("window", "batch")}
           for e in get_recorder().events[mark:]}
    assert got == {"inner": {"window": 3, "batch": 5},
                   "outer": {"window": 9, "batch": None},
                   "second": {"window": 4, "batch": None},
                   "third": {"window": 3, "batch": None},
                   "after": {"window": None, "batch": None}}

    # another thread's spans carry none of this thread's identifiers
    seen = []

    def elsewhere():
        with span("dl4j.test.elsewhere") as s:
            pass
        seen.append(s.ids)

    with identified(window=1):
        t = threading.Thread(target=elsewhere)
        t.start()
        t.join()
    assert seen == [{}]


# --------------------------------------------------- the prefetch's counters
class Gated:
    """An iterable whose producer side waits for the test before each item."""

    def __init__(self, n):
        self.n = n
        self.go = [threading.Event() for _ in range(n + 1)]  # [n]: the end
        self.made = [threading.Event() for _ in range(n)]

    def __iter__(self):
        for i in range(self.n):
            self.go[i].wait(10)
            self.made[i].set()
            yield i
        self.go[self.n].wait(10)


def wait_for(predicate, what):
    deadline = threading.Event()
    for _ in range(2000):
        if predicate():
            return
        deadline.wait(0.005)
    raise AssertionError(f"never saw {what}")


@pytest.fixture
def queues(monkeypatch):
    """The prefetch queues made meanwhile, each saying (``blocking``) when
    its consumer has found it empty and gone into its get."""
    import queue
    import types

    from deeplearning4j_tpu.utils import collections as dl4j_collections

    made = []

    class Announcing(queue.Queue):
        def __init__(self, maxsize=0):
            super().__init__(maxsize)
            self.blocking = threading.Event()
            made.append(self)

        def get(self, block=True, timeout=None):
            if self.empty():
                self.blocking.set()
            return super().get(block, timeout)

    monkeypatch.setattr(dl4j_collections, "queue", types.SimpleNamespace(
        Queue=Announcing, Empty=queue.Empty, Full=queue.Full))
    return made


def test_a_get_counts_ready_when_an_item_waits_and_empty_when_it_blocks(
        queues):
    base = Gated(3)
    before = counted()
    moved = lambda: added(counted(), before)  # noqa: E731
    it = iter(AsyncIterator(base, queue_size=2))

    # a slow producer: the consumer asks first, finds nothing and goes into
    # the blocking get; only then is the item made
    got = []
    consumer = threading.Thread(target=lambda: got.append(next(it)))
    consumer.start()
    wait_for(lambda: len(queues) == 1, "the prefetch queue")
    (q,) = queues
    assert q.blocking.wait(10)
    assert not base.made[0].is_set() and got == []
    base.go[0].set()
    consumer.join(10)
    assert got == [0]
    assert moved()["dl4jtpu_iterator_gets_total", "empty"] == 1
    assert moved()["dl4jtpu_iterator_gets_total", "ready"] == 0

    # a fast producer: both items are in the queue before the consumer asks
    base.go[1].set()
    base.go[2].set()
    wait_for(lambda: q.qsize() == 2, "two items waiting")
    assert [next(it), next(it)] == [1, 2]
    assert moved()["dl4jtpu_iterator_gets_total", "ready"] == 2
    assert moved()["dl4jtpu_iterator_gets_total", "empty"] == 1
    assert moved()["dl4jtpu_iterator_produce_seconds", ""] == 3

    # the end sentinel is a get too, here one the consumer finds waiting
    base.go[3].set()
    wait_for(lambda: q.qsize() == 1, "the sentinel waiting")
    assert list(it) == []
    assert moved()["dl4jtpu_iterator_gets_total", "ready"] == 3


def test_a_producer_that_finds_the_queue_full_counts_its_wait(queues):
    base = Gated(3)
    before = counted()
    full = ("dl4jtpu_iterator_queue_full_seconds", "")
    waited = lambda: added(counted(), before).get(full, 0)  # noqa: E731
    it = iter(AsyncIterator(base, queue_size=1))
    base.go[0].set()
    assert next(it) == 0
    (q,) = queues
    # item 1 finds the queue of one empty: a put that finds room counts nothing
    base.go[1].set()
    wait_for(lambda: q.qsize() == 1, "item 1 waiting")
    assert waited() == 0
    # item 2 finds it full, and its wait is counted when it ends
    base.go[2].set()
    wait_for(base.made[2].is_set, "item 2 made")
    assert waited() == 0
    base.go[3].set()
    assert list(it) == [1, 2]
    assert waited() >= 1


def test_the_async_dataset_iterator_hands_batches_on_by_reference(queues):
    data = ListDataSetIterator(sc.batches("per_batch")[:3])
    before = counted()
    walk = iter(AsyncDataSetIterator(data, queue_size=8))
    first = next(walk)
    assert first is data._data[0]       # by reference: the pump copies nothing
    (q,) = queues
    wait_for(lambda: q.qsize() == 3, "two batches and the sentinel")
    assert [ds is kept for ds, kept in zip(walk, data._data[1:])] == [True] * 2
    moved = added(counted(), before)
    assert moved["dl4jtpu_iterator_gets_total", "ready"] \
        + moved["dl4jtpu_iterator_gets_total", "empty"] == 4
    assert moved["dl4jtpu_iterator_gets_total", "ready"] >= 3
    assert moved["dl4jtpu_iterator_produce_seconds", ""] == 3


def test_a_plain_iterator_is_produced_inside_next_batch():
    """No prefetch thread: the production itself is the wait. And a net with
    no listener opens no ``dl4j.fit.listeners`` on the per-batch path."""
    net = sc.net("per_batch", "mln")

    class Plain:
        prefetch_supported = False

        def __iter__(self):
            return iter(sc.batches("per_batch")[:2])

    mark = len(get_recorder().events)
    net.fit(Plain())
    events = get_recorder().events[mark:]
    waits = [e for e in events if e["name"] == "dl4j.fit.next_batch"]
    assert [e["args"]["batch"] for e in waits] == [0, 1, 2]
    assert [e["args"]["batch"] for e in events
            if e["name"] == "dl4j.fit.step"] == [0, 1]
    assert not [e for e in events if e["name"] == "dl4j.fit.listeners"]
    assert net.iteration == 2 and np.isfinite(net.score())
